"""The harness end to end at smoke size on the CPU: a sound run is correct
and reports its cell's metrics; a run whose timed path is broken underneath
is not correct; a run with no chip prints no result."""

import os
import shutil
import subprocess
import sys

import pytest

from bench.faults import FAULTS
from conftest import ROOT, smoke_cell

CELLS = ["lstm-prefill-heavy", "mamba-decode-heavy", "lstm-decode-heavy"]
SEED = 2**31 + 17


def run(workload, seconds=1.0, trace=False):
    from bench import harness

    return harness.run_cell(smoke_cell(workload), SEED, seconds, trace,
                            require_tpu=False, log=lambda *a, **k: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    from bench import harness

    out = run(workload)
    assert out["correct"], out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.load_cell(workload).metrics}
    assert set(out["metrics"]) == want
    assert out["checks"]["compared_tokens"]["value"] >= 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(workload)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    """The bfloat16 control, put in the program's place, is not correct,
    while the program is.  On the CPU the program's float32 is exact, so at
    this size every gap number reads 0 and each limit is 0; the cell's own
    limits are set from chip readings at its size (PERF.md).  A larger
    vocabulary and longer requests than the other smoke runs give the
    near-ties that rounding flips."""
    from bench import harness

    cell = smoke_cell(workload, grid=(32, 64), outputs=(16, 24), width=128, vocab=4096)
    for name in ("max_logit_gap", "mean_logit_gap", "flip_share"):
        cell.data["limits"][name] = 0.0
    out = harness.run_cell(cell, SEED, 1.0, False, require_tpu=False, control=True,
                           log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    ctrl = out["control"]
    assert ctrl["checks"]["compared_tokens"] == out["checks"]["compared_tokens"]
    assert ctrl["correct"] is False, ctrl["checks"]


def test_no_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_bare_checkout_exits_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
