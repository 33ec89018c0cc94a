#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``); the numbers compared for ``correct`` end standard
error.  Exits 2 with no result where JAX finds no TPU.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402 -- needs the paths above

if __name__ == "__main__":
    sys.exit(harness.main())
