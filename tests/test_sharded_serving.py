"""Mesh-aware serving (README §Sharded serving), each scenario in a
subprocess with 8 forced host devices so the main test process keeps the
single real CPU device.

The subprocess scripts assert the hard guarantees of the ShardPlan refactor:
token-for-token greedy parity sharded vs unsharded (both decode drivers),
shard-affine prefix-cache placement, per-shard quarantine isolation,
mesh-keyed synthesis caching with the gate-boundary all-reduce, and the
trace-replay load generator's cross-topology digest parity + per-shard
accounting."""

import os
import subprocess
import sys

import pytest

SCRIPTS = os.path.join(os.path.dirname(__file__), "multidevice_scripts")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, tokens: list[str], timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
    for token in tokens:
        assert token in proc.stdout, f"{script}: missing {token}\n{proc.stdout}"


@pytest.mark.slow
def test_sharded_serving_subprocess():
    """dp=8 greedy token parity (per-token + persistent drivers),
    shard-affine prefix-cache placement, per-shard quarantine isolation."""
    _run("run_sharded_serving.py",
         ["PARITY_OK", "AFFINITY_OK", "QUARANTINE_OK"])


@pytest.mark.slow
def test_sharded_moe_serving_subprocess():
    """(data=2, model=4): the held experts divide over ``model`` and run as a
    shard_map; greedy tokens equal the unsharded server's, both drivers,
    with all experts held and with half of them."""
    _run("run_sharded_moe_serving.py", ["MOE_EP_OK", "MOE_EP_CUT_OK"])


@pytest.mark.slow
def test_sharded_synthesis_subprocess():
    """Mesh-aware synthesize()/backends: TP all-reduce at the gate
    boundary, pallas shard_map over the data axis, mesh-keyed memo."""
    _run("run_sharded_synthesis.py",
         ["SYNTH_TP_OK", "SYNTH_PALLAS_OK", "SYNTH_CACHE_OK"])


@pytest.mark.slow
def test_sharded_loadgen_subprocess():
    """Trace replay across dp=1 / folded / sharded topologies: identical
    token digests, valid repro.loadgen/v1 reports, per-shard accounting."""
    _run("run_sharded_loadgen.py", ["LOADGEN_OK"])
