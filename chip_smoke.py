#!/usr/bin/env python3
"""On-chip smoke test of the paper's main path at paper-lstm's full width.

    python chip_smoke.py [--seed N]          # one TPU chip
    python chip_smoke.py --four-chips        # sharded serving on 4 chips

Runs in ONE process (a chip belongs to one process at a time) and refuses
to run anywhere but a TPU.  Default phases, in order:

* serve — ``DecodeServer`` over the full ``paper-lstm`` CONFIG (8 layers,
  d_model = rnn_hidden = 1024, vocab 32000) with ``use_codegen=True``, so
  every prompt's prefill runs the generated LSTM stage kernel; random
  weights from ``--seed``; 8 slots, ``max_seq=512``, persistent K=8 decode
  blocks; 8 requests whose prompt lengths include the primes 37 and 257,
  32 new tokens each.  Every request must retire on ``max_tokens``/``eos``.
  The same requests are served twice: the first pass includes compilation,
  the second runs on compiled programs and must emit the same tokens.
* check — each prompt's last-token prefill logits against the plain
  ``jnp`` path (``use_codegen=False``) under
  ``jax.default_matmul_precision("highest")``; the server's first token
  must be the argmax of the kernel path's logits, and the compiled prefill
  must contain the Mosaic kernel (``tpu_custom_call``).
* synthesis — ``synthesize(NetworkSpec(cell="lstm", 1024 → 1024, ...),
  backend="pallas")`` must report ``backend == "pallas"`` with no fallback,
  and the pallas forward must match the xla forward (run at highest
  precision).

``--four-chips`` runs only the sharded-serving comparison: one seeded
``loadgen`` trace through a ``ShardPlan(make_local_mesh(dp=4, tp=1))``
server (sharded layout) and through a plan-less server on device 0.  The
token digests must be equal and every decode-cache leaf must be split over
4 distinct devices.

Lines before the last are information (``[info]``/``[check]``).  The last
line is the verdict, ``{"ok": true, "device": {...}}``, printed only when
every phase passed; any failure exits non-zero.

Tolerance.  The served path runs at default matmul precision: on a TPU an
f32 dot (XLA's, and the kernel's Mosaic ``x @ W``) may round its operands
to bfloat16, unit roundoff ``u = 2**-8``.  A K-term dot with random-sign
terms then errs by about ``2u`` of its output's scale; a logit sits
downstream of ~6 such dots (gate contraction, out-projection, per layer,
partly damped by the gates) and the tied-embedding head.  The check
allows ``LOGIT_RTOL = 8u`` of the reference's largest logit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch import compile_cache  # noqa: E402 — needs the src path

U_BF16 = 2.0 ** -8
LOGIT_RTOL = 8 * U_BF16
PROMPT_LENS = (37, 257, 2, 64, 101, 13, 128, 199)
NEW_TOKENS = 32
SLOTS, MAX_SEQ, BLOCK_K = 8, 512, 8


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    print(f"[check] {'pass' if ok else 'FAIL'}: {msg}", flush=True)
    if not ok:
        raise SystemExit(1)


def tpu_devices():
    """The TPU devices, or exit 2 (no verdict) when JAX found none."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "this test runs only on the chip", file=sys.stderr)
        raise SystemExit(2)
    return devs


def full_config(codegen: bool):
    from repro.configs import get_config

    return dataclasses.replace(get_config("paper-lstm"), use_codegen=codegen)


def max_rel_diff(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def phase_serve_and_check(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.runtime import DecodeServer, Request

    cfg = full_config(codegen=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(lm.init_params(cfg, jax.random.PRNGKey(seed)))
    info(f"paper-lstm params: {sum(x.size for x in jax.tree.leaves(params)):,} "
         f"(init {time.perf_counter() - t0:.2f}s)")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=n).tolist() for n in PROMPT_LENS]

    server = DecodeServer(cfg, params, num_slots=SLOTS, max_seq=MAX_SEQ,
                          persistent=True, block_k=BLOCK_K, seed=seed)
    outs = []
    for rnd in range(2):
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            server.submit(Request(uid=rnd * 100 + i, prompt=p,
                                  max_new_tokens=NEW_TOKENS))
        done = server.run_until_drained()
        wall = time.perf_counter() - t0
        got = {r.uid % 100: r for r in done if r.uid // 100 == rnd}
        check(len(got) == len(prompts),
              f"serve pass {rnd}: {len(got)}/{len(prompts)} requests retired")
        reasons = sorted({r.finish_reason for r in got.values()})
        check(set(reasons) <= {"max_tokens", "eos"},
              f"serve pass {rnd}: finish reasons {reasons}")
        check(all(len(r.out_tokens) == NEW_TOKENS or r.finish_reason == "eos"
                  for r in got.values()),
              f"serve pass {rnd}: every request got {NEW_TOKENS} tokens")
        toks = sum(len(r.out_tokens) for r in got.values())
        label = "compile+run" if rnd == 0 else "run (compiled)"
        info(f"serve pass {rnd} ({label}): {wall:.3f}s wall, {toks} tokens, "
             f"prompt lens {list(PROMPT_LENS)}")
        outs.append([got[i].out_tokens for i in range(len(prompts))])
    check(outs[0] == outs[1], "second serve pass emits the same tokens")

    # check: kernel path vs the plain jnp path at highest precision
    prefill = jax.jit(lambda p, t: lm.prefill(p, cfg, t))
    ref_cfg = full_config(codegen=False)
    ref_prefill = jax.jit(lambda p, t: lm.prefill(p, ref_cfg, t))
    toks0 = jnp.asarray(np.asarray(prompts[0], np.int32)[None])
    t0 = time.perf_counter()
    hlo = prefill.lower(params, toks0).compile().as_text()
    info(f"prefill compile (T={PROMPT_LENS[0]}): "
         f"{time.perf_counter() - t0:.3f}s")
    check("tpu_custom_call" in hlo,
          "compiled prefill HLO contains tpu_custom_call (Mosaic kernel)")
    worst = 0.0
    for i, p in enumerate(prompts):
        t = jnp.asarray(np.asarray(p, np.int32)[None])
        got = np.asarray(prefill(params, t)[0][0])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(ref_prefill(params, t)[0][0])
        rel = max_rel_diff(got, ref)
        worst = max(worst, rel)
        info(f"prompt len {len(p):3d}: max|logit diff| = "
             f"{float(np.max(np.abs(got - ref))):.6g}, max|ref logit| = "
             f"{float(np.max(np.abs(ref))):.6g}, rel = {rel:.6g}")
        check(int(np.argmax(got)) == outs[0][i][0],
              f"prompt len {len(p)}: served first token is the argmax of "
              "the kernel path's logits")
    check(worst <= LOGIT_RTOL,
          f"prefill logits vs highest-precision jnp reference: worst rel "
          f"diff {worst:.6g} <= {LOGIT_RTOL:.6g} (8 * 2**-8)")


def phase_synthesis(seed: int) -> None:
    import jax
    import numpy as np

    from repro import codegen
    from repro.core.synthesis import NetworkSpec, synthesize

    spec = NetworkSpec(num_inputs=1024, num_hidden_layers=2,
                       nodes_per_layer=1024, num_outputs=16, cell="lstm",
                       seq_len=37, seed=seed)
    batch = 8
    t0 = time.perf_counter()
    rep = synthesize(spec, batch=batch, backend="pallas")
    info(f"synthesize({spec.name}, pallas): {time.perf_counter() - t0:.3f}s "
         f"wall, compile {rep.compile_s:.3f}s")
    check(rep.backend == "pallas" and rep.fallback_from is None,
          f"synthesize reports backend={rep.backend!r}, "
          f"fallback_from={rep.fallback_from!r}")
    u = np.random.default_rng(seed).uniform(
        -1, 1, (batch, spec.seq_len, spec.num_inputs)).astype(np.float32)
    params, fwd = codegen.compile_spec(spec, "pallas")
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(jax.jit(fwd)(params, u)))
    info(f"pallas forward (compile+run): {time.perf_counter() - t0:.3f}s")
    xparams, xfwd = codegen.compile_spec(spec, "xla")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(xfwd)(xparams, u))
    rel = max_rel_diff(got, ref)
    info(f"synthesis pallas vs xla: max|diff| = "
         f"{float(np.max(np.abs(got - ref))):.6g}, max|ref| = "
         f"{float(np.max(np.abs(ref))):.6g}, rel = {rel:.6g}")
    check(rel <= LOGIT_RTOL, f"pallas matches xla: rel {rel:.6g} <= "
          f"{LOGIT_RTOL:.6g}")


def phase_four_chips(seed: int) -> None:
    import jax

    from repro.launch.mesh import make_local_mesh
    from repro.models import lm
    from repro.runtime import (DecodeServer, ShardPlan, TraceSpec, make_trace,
                               replay)

    devs = jax.devices()
    check(len(devs) >= 4, f"{len(devs)} TPU devices (need 4)")
    cfg = full_config(codegen=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    trace = make_trace(TraceSpec(num_requests=16, max_new_tokens=16,
                                 vocab=cfg.vocab, seed=seed))
    kw = dict(num_slots=SLOTS, max_seq=MAX_SEQ, persistent=True,
              block_k=BLOCK_K, seed=seed)
    reports = {}
    for name in ("sharded_dp4", "single_device0"):
        if name == "sharded_dp4":
            srv = DecodeServer(cfg, params, plan=ShardPlan(
                make_local_mesh(dp=4, tp=1)), **kw)
            leaves = jax.tree.leaves(srv.caches)
            spread = [len({s.device for s in leaf.addressable_shards})
                      for leaf in leaves]
            split = all(not leaf.sharding.is_fully_replicated
                        for leaf in leaves)
            check(min(spread) == 4 and split,
                  f"{len(leaves)} cache leaves each split over 4 devices "
                  f"(devices per leaf: {sorted(set(spread))})")
        else:
            srv = DecodeServer(cfg, jax.device_put(params, devs[0]), **kw)
            on = {s.device for leaf in jax.tree.leaves(srv.caches)
                  for s in leaf.addressable_shards}
            check(on == {devs[0]}, f"plan-less server state on {sorted(on, key=str)}")
        t0 = time.perf_counter()
        rep = replay(srv, trace)
        info(f"{name}: {rep['completed']}/{rep['requests']} requests, "
             f"{rep['decoded_tokens']} tokens, {time.perf_counter() - t0:.3f}s "
             f"wall (compile+run), digest {rep['tokens_digest']}, "
             f"by_reason {rep['by_reason']}")
        check(rep["completed"] == rep["requests"],
              f"{name}: every request completed")
        reports[name] = rep
    a, b = (reports[k]["tokens_digest"] for k in ("sharded_dp4",
                                                   "single_device0"))
    check(a == b, f"token digests equal: sharded {a} == single {b}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-serving comparison")
    args = ap.parse_args()

    cache_dir = compile_cache.enable()
    devs = tpu_devices()
    info(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
         f"{cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_serve_and_check(args.seed)
        phase_synthesis(args.seed)
    info(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
