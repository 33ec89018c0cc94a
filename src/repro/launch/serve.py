"""Serving launcher: continuous-batching decode server with a synthetic
request stream (see examples/serve_batched.py for the walkthrough).

    python -m repro.launch.serve --arch falcon-mamba-7b --smoke --requests 16

``--smoke`` serves the reduced CPU-sized config; without it the arch's
full ``CONFIG`` is served.

Observability (README §Observability):

    python -m repro.launch.serve --smoke --trace-out trace.json --metrics-out metrics.json

``--trace-out`` enables span tracing and writes a Chrome-trace-event JSON
loadable in Perfetto (https://ui.perfetto.dev); ``--metrics-out`` writes the
metrics-registry snapshot + predicted-vs-measured ledger, schema-checkable
with ``python -m repro.obs.check``.

Sharded serving (README §Sharded serving) — requires a device pool, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU:

    python -m repro.launch.serve --smoke --mesh 8x1 --slots 8 \\
        --loadgen --loadgen-out loadgen.json

``--mesh DPxTP`` maps the slot pool onto a device mesh (``--mesh-layout
folded`` keeps the shards logical and decodes them through one fused
dispatch — the single-host C-slow composition); ``--loadgen`` replaces the
fixed synthetic stream with the seeded trace replay from
``repro.runtime.loadgen`` and ``--loadgen-out`` writes the
``repro.loadgen/v1`` report, also checkable with ``repro.obs.check``.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU); default is the full CONFIG")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--persistent", action="store_true",
                    help="device-side K-step decode blocks (1 sync / K tokens)")
    ap.add_argument("--block-k", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: N prompt tokens per tick (0 = off)")
    ap.add_argument("--prefill-adaptive", action="store_true",
                    help="drain whole prefill jobs on ticks with no live "
                         "decode slot (chunk bound applies only under "
                         "contention)")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="MB",
                    help="radix prefix-cache byte budget in MB (0 = off)")
    ap.add_argument("--scheduler", choices=["priority", "fifo"],
                    default="priority")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL (expired:queue / expired:decode)")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="stall watchdog bound in seconds: no serving "
                         "progress past the bound aborts in-flight work "
                         "with finish_reason='error:stalled'")
    ap.add_argument("--shed", action="store_true",
                    help="reject the lowest-priority class when queue "
                         "waits become unserviceable")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable tracing; write Perfetto-loadable trace JSON")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write metrics snapshot + ledger JSON")
    ap.add_argument("--mesh", default=None, metavar="DPxTP",
                    help="shard the server over a device mesh, e.g. 8x1 "
                         "(slots over the data axis, gate contractions over "
                         "model); needs dp*tp devices")
    ap.add_argument("--mesh-layout", choices=["sharded", "folded"],
                    default="sharded",
                    help="'sharded' partitions the slot batch across "
                         "devices (real hardware); 'folded' keeps shards "
                         "logical and decodes them through one fused "
                         "dispatch (single-host C-slow composition)")
    ap.add_argument("--loadgen", action="store_true",
                    help="replay a seeded load-generator trace (Poisson "
                         "arrivals, mixed prompt lengths, shared-prefix "
                         "fleets) instead of the fixed synthetic stream")
    ap.add_argument("--loadgen-seed", type=int, default=0)
    ap.add_argument("--loadgen-out", default=None, metavar="PATH",
                    help="write the repro.loadgen/v1 replay report JSON")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()

    import json
    import time

    import jax
    import numpy as np

    from repro import obs as obs_lib
    from repro.configs import get_config, get_smoke_config
    from repro.models import lm
    from repro.obs import log
    from repro.runtime import (DecodeServer, Request, SchedulerConfig,
                               ShardPlan, loadgen)

    plan = None
    if args.mesh:
        from repro.launch.mesh import make_local_mesh

        try:
            dp, tp = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"--mesh must look like 8x1, got {args.mesh!r}")
        plan = ShardPlan(make_local_mesh(dp=dp, tp=tp),
                         fold_data=args.mesh_layout == "folded")
        log.info(f"mesh: {plan.describe()}")

    obs = obs_lib.Observability(trace=bool(args.trace_out))
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    server = DecodeServer(cfg, params, num_slots=args.slots, max_seq=args.max_seq,
                          block_k=args.block_k, persistent=args.persistent,
                          prefill_chunk=args.prefill_chunk,
                          prefill_adaptive=args.prefill_adaptive,
                          prefix_cache_bytes=args.prefix_cache << 20,
                          scheduler=SchedulerConfig(policy=args.scheduler,
                                                    shed=args.shed),
                          obs=obs, watchdog_s=args.watchdog_s, plan=plan)
    t0 = time.perf_counter()
    report = None
    if args.loadgen:
        spec = loadgen.TraceSpec(num_requests=args.requests,
                                 max_new_tokens=args.max_new,
                                 vocab=cfg.vocab, seed=args.loadgen_seed)
        report = loadgen.replay(server, loadgen.make_trace(spec))
        done = server.completed
        wall, toks = report["wall_s"], report["decoded_tokens"]
    else:
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            server.submit(Request(
                uid=i, prompt=list(rng.integers(1, cfg.vocab, size=int(rng.integers(2, 10)))),
                max_new_tokens=args.max_new, deadline_s=args.deadline_s))
        done = server.run_until_drained()
        wall = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
    stats = server.stats()
    health = stats["health"]
    log.info(f"served {len(done)} requests, {toks} tokens, {wall:.2f}s "
             f"({toks / wall:.1f} tok/s, "
             f"{stats['syncs_per_token']:.3f} syncs/token)")
    log.info(f"health: {health['status']} "
             f"(quarantined={health['quarantined_slots']}, "
             f"stalled_events={health['stalled_events']}, "
             f"queued={health['queued']})")
    if report is not None:
        log.info(f"loadgen: {report['completed']}/{report['requests']} done "
                 f"in {report['ticks']} ticks, "
                 f"{report['throughput_tok_s']:.1f} tok/s, "
                 f"digest={report['tokens_digest']}")
        if args.loadgen_out:
            with open(args.loadgen_out, "w") as fh:
                json.dump(report, fh, indent=1)
            log.info(f"wrote loadgen report -> {args.loadgen_out}")
    if args.trace_out:
        obs.export_trace(args.trace_out)
        log.info(f"wrote trace ({len(obs.tracer.events())} events) -> "
                 f"{args.trace_out}")
    if args.metrics_out:
        # the serve-side registry snapshot, plus a ledger: the serve scope's
        # own (per-shard loadgen rows) when it recorded anything, else the
        # process-global one (synthesis predicted-vs-measured rows)
        obs.export_metrics(args.metrics_out, stats=stats,
                           ledger=obs.ledger if len(obs.ledger)
                           else obs_lib.OBS.ledger)
        log.info(f"wrote metrics snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
