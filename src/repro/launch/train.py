"""Production training launcher.

    python -m repro.launch.train --arch smollm-135m --steps 100 [--smoke]

On a real TPU pod this runs under the production mesh with the cell's
sharding plan; on CPU it uses the local mesh.  Supports resume, failure
injection (for drills), and metrics dumping.  See examples/train_lm.py for
the walkthrough version.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="failure-injection drill: raise at this step")
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()

    from repro import optim
    from repro.configs import get_config, get_smoke_config
    from repro.data import DataConfig
    from repro.runtime import Trainer, TrainerConfig

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, microbatches=args.microbatches,
        fail_at_step=args.fail_at,
    )
    ocfg = optim.AdamWConfig(lr_peak=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                             total_steps=args.steps)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)

    from repro.obs import log

    trainer = Trainer(cfg, tcfg, ocfg, dcfg)
    res = trainer.run(resume=not args.no_resume)
    log.info(f"final_loss={res['final_loss']:.4f} "
             f"entropy_floor={res['entropy_floor']:.4f}")
    if args.metrics_out:
        trainer.dump_metrics(args.metrics_out)


if __name__ == "__main__":
    main()
