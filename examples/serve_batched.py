"""Batched serving demo: slot-based continuous batching over the decode
state-space step, with per-request latency stats.

    python -m examples.serve_batched --arch falcon-mamba-7b --smoke --requests 12

``--smoke`` serves the reduced CPU-sized config; without it the arch's
full ``CONFIG``.
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import lm
from repro.runtime import DecodeServer, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU); default is the full CONFIG")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 serving (paper's fixed-point stage)")
    ap.add_argument("--codegen", action="store_true",
                    help="route recurrent prefill through the generated "
                         "fused cell kernel (repro.codegen fast path)")
    ap.add_argument("--persistent", action="store_true",
                    help="persistent device-side decode: one jitted K-step "
                         "loop per dispatch, one host sync per K tokens")
    ap.add_argument("--block-k", type=int, default=8,
                    help="decode steps per persistent block (the serving "
                         "unroll knob)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: consume prompts N tokens/tick, "
                         "interleaved with decode (0 = one-shot prefill)")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="MB",
                    help="radix prefix cache byte budget in MB (0 = off); "
                         "shared-prefix admissions splice stored state")
    ap.add_argument("--scheduler", choices=["priority", "fifo"],
                    default="priority",
                    help="request scheduler policy (priority classes + "
                         "fairness aging, or plain FIFO)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL: expired requests retire with "
                         "finish_reason='expired:queue'/'expired:decode'")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="stall watchdog bound: no serving progress for this "
                         "many seconds aborts in-flight work (error:stalled)")
    ap.add_argument("--shed", action="store_true",
                    help="scheduler load shedding: reject the lowest-"
                         "priority class when deadline math says the queue "
                         "is unserviceable")
    ap.add_argument("--mesh", default=None, metavar="DPxTP",
                    help="shard the server over a device mesh (e.g. 8x1: "
                         "slot pools over the data axis, gate contractions "
                         "over model); needs dp*tp devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    ap.add_argument("--mesh-layout", choices=["sharded", "folded"],
                    default="sharded",
                    help="'sharded' partitions slots across devices; "
                         "'folded' decodes all shards through one fused "
                         "dispatch (single-host C-slow composition)")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.codegen:
        import dataclasses

        cfg = dataclasses.replace(cfg, use_codegen=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    if args.int8:
        from repro.runtime.quantized import dequantize_lm_params, quantize_lm_params

        qp, stats = quantize_lm_params(params)
        print(f"int8 weights: {stats['weights_quantized']} tensors, "
              f"{stats['compression']:.2f}x compression "
              f"({stats['bytes_before']/1e6:.1f} -> {stats['bytes_after']/1e6:.1f} MB)")
        params = dequantize_lm_params(qp)  # W8A16: dense compute, int8 storage
    from repro.runtime import SchedulerConfig

    plan = None
    if args.mesh:
        from repro.launch.mesh import make_local_mesh
        from repro.runtime import ShardPlan

        dp, tp = (int(x) for x in args.mesh.lower().split("x"))
        plan = ShardPlan(make_local_mesh(dp=dp, tp=tp),
                         fold_data=args.mesh_layout == "folded")
        print(f"mesh: {plan.describe()}")

    server = DecodeServer(cfg, params, num_slots=args.slots, max_seq=args.max_seq,
                          block_k=args.block_k, persistent=args.persistent,
                          prefill_chunk=args.prefill_chunk,
                          prefix_cache_bytes=args.prefix_cache << 20,
                          scheduler=SchedulerConfig(policy=args.scheduler,
                                                    shed=args.shed),
                          watchdog_s=args.watchdog_s, plan=plan)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(2, 8))
        server.submit(Request(
            uid=i,
            prompt=list(rng.integers(1, cfg.vocab, size=plen)),
            max_new_tokens=args.max_new,
            deadline_s=args.deadline_s,
        ))
    done = server.run_until_drained()
    wall = time.perf_counter() - t0

    toks = sum(len(r.out_tokens) for r in done)
    served = [r for r in done if r.first_token_at is not None]  # admission may reject
    ttfts = [r.first_token_at - r.submitted_at for r in served]
    lats = [r.done_at - r.submitted_at for r in served]
    stats = server.stats()
    mode = f"persistent(K={args.block_k})" if args.persistent else "per-token"
    print(f"arch={cfg.name} slots={args.slots} requests={len(done)} mode={mode}")
    print(f"generated {toks} tokens in {wall:.2f}s -> {toks / wall:.1f} tok/s "
          f"({stats['syncs_per_token']:.3f} host syncs/token)")
    if args.prefill_chunk:
        pf = stats["prefill"]
        print(f"prefill chunk={args.prefill_chunk}: {pf['chunks_run']} chunks, "
              f"max {pf['max_prompt_steps_per_tick']} prompt steps/tick")
    if args.prefix_cache:
        pc = stats["prefix_cache"]
        print(f"prefix cache: {pc['hits']} hits / {pc['partial_hits']} partial "
              f"/ {pc['misses']} misses, {pc['prompt_steps_saved']} prompt "
              f"steps saved, {pc['bytes_in_use'] / 1e6:.1f} MB")
    if served:
        print(f"TTFT   p50={np.percentile(ttfts, 50)*1e3:.0f}ms p95={np.percentile(ttfts, 95)*1e3:.0f}ms")
        print(f"E2E    p50={np.percentile(lats, 50)*1e3:.0f}ms p95={np.percentile(lats, 95)*1e3:.0f}ms")
    if plan is not None:
        mesh_stats = stats["mesh"]
        print(f"mesh dp={mesh_stats['dp']} tp={mesh_stats['tp']} "
              f"layout={mesh_stats['layout']}: tokens/shard="
              f"{mesh_stats['decoded_tokens_by_shard']}")
    health = stats["health"]
    print(f"health: {health['status']} (quarantined={health['quarantined_slots']}, "
          f"stalled_events={health['stalled_events']})")
    reasons = {}
    for r in done:
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    if set(reasons) - {"eos", "max_tokens"}:
        print(f"finish reasons: {reasons}")
    for r in done[:3]:
        print(f"  req{r.uid}: prompt={r.prompt} -> {r.out_tokens}")


if __name__ == "__main__":
    main()
