#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same run at several fixed rates, on the chip.

    python3 bench/sweep.py --workload <name> --seconds <s> --seed <n> --rates 4 8 12 ...

One process, one run per rate (the rate replaces the cell's ``rate_rps``).
For each rate it prints the offered and completed request rates, the latency
percentiles, and the share of requests due in the window that got their
first token before the window closed; a rate is sustained where that share
stays near 1 and the queue wait stays bounded.  The cell's rate is then set
by hand, once, to about four fifths of the highest sustained rate.
"""

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402 -- needs the paths above


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    base = harness.load_cell(args.workload)
    extra = [{"name": n, "unit": "ms"} for n in ("queue_wait_p95_ms.prefill", "gen_lag_p99_ms")]
    rows = []
    for rate in args.rates:
        cell = copy.copy(base)
        cell.data = dict(base.data, rate_rps=rate)
        cell.metrics = base.metrics + extra
        out = harness.run_cell(cell, args.seed, args.seconds, False, log=lambda *a, **k: None)
        row = {"rate_rps": rate, "attempted": out["attempted"], "failed": out["failed"],
               "correct": out["correct"], **out["load"],
               **{k: v["value"] for k, v in out["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out_dir = ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"sweep_{args.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
