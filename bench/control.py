#!/usr/bin/env python3
"""Read the program's and the control's gap numbers on many seeds, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 ... [--fault <name>]

One process (a chip belongs to one process): for each seed, one run of the
cell as the benchmark makes it, then the comparison on its sample, and the
bfloat16 control judged on the same sample by the same limits.  The
program's readings over the seeds give each limit's lower reading, the
control's its upper one.  With ``--fault`` (a name in ``bench/faults.py``)
the fault is planted in the program and only the program is read.  Writes
``bench_out/control_<workload>[_<fault>].json``; benchmark runs never run
this.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import faults, harness  # noqa: E402 -- needs the paths above


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    if args.fault:
        import pytest

        faults.FAULTS[args.fault](pytest.MonkeyPatch())
    rows = []
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, control=not args.fault,
                               log=lambda *a, **k: None)
        row = {"seed": seed, "correct": out["correct"], "program": out["compare"],
               "compared_tokens": out["checks"]["compared_tokens"]["value"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"], "load": out["load"]}
        if not args.fault:
            row["control_correct"] = out["control"]["correct"]
            row["control"] = out["control"]["stats"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = rows[0]["program"]
    summary = {"workload": args.workload, "fault": args.fault,
               "program_max": {k: max(r["program"][k] for r in rows) for k in names}}
    if not args.fault:
        summary["control_min"] = {k: min(r["control"][k] for r in rows) for k in names}
    print(json.dumps(summary), flush=True)
    out_dir = ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    tag = f"_{args.fault}" if args.fault else ""
    (out_dir / f"control_{args.workload}{tag}.json").write_text(
        json.dumps({"rows": rows, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
