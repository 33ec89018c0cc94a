#!/usr/bin/env python3
"""Run one cell traced, with the server's own spans on, and split the device's
idle time by what the server was doing.

    python3 bench/span_idle.py --workload <name> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``'s, with the server built with
``Observability(trace=True)``: its spans (``repro.*``) land in the same
profiler trace, and ``bench/spans.py`` attributes each piece of device idle
time to them.  Prints the run's result line with one more key, ``spans``:
the idle split by span, the span metrics, and the share of the idle inside
``bench.step_block`` that program spans hold.  Exits 2 with no result where
JAX finds no TPU.  Benchmark runs never run this.
"""

import argparse
import functools
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import devtrace, harness, spans  # noqa: E402 -- needs the paths above


def run(cell, seed: int, seconds: float, **kw) -> dict:
    """One traced run of ``cell``, its result with ``spans`` added."""
    from repro import obs, runtime

    found = {}
    reduce_dir = devtrace.reduce_dir

    def reduce_both(trace_dir, programs):
        found["spans"] = spans.reduce_dir(trace_dir)
        return reduce_dir(trace_dir, programs)

    server = functools.partial(runtime.DecodeServer, obs=obs.Observability(trace=True))
    with mock.patch.object(runtime, "DecodeServer", server), \
            mock.patch.object(devtrace, "reduce_dir", reduce_both):
        out = harness.run_cell(cell, seed, seconds, True, **kw)
    s = found["spans"]
    out["spans"] = {"idle_by_span": s.by_span(), "metrics": s.metrics(),
                    "program_share_of_step_block": s.program_share()}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    log = functools.partial(print, file=sys.stderr, flush=True)
    try:
        out = run(harness.load_cell(args.workload), args.seed, args.seconds, log=log)
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 2
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in out["checks"].items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
