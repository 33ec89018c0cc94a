"""Idle time by the program's spans, on a synthetic trace worked by hand."""

import pytest

from bench import devtrace, spans
from bench.devtrace import Event

D, H = "/device:TPU:0", "/host:CPU"


def ev(plane, name, s_us, e_us, line="t"):
    return Event(plane, line, name, s_us * 1e3, (e_us - s_us) * 1e3)


def harness_and_device():
    return [
        ev(H, "bench.window", 0, 1000),
        ev(H, "bench.step_block", 100, 600),
        ev(H, "bench.step_block", 600, 700),
        ev(H, "bench.submit", 800, 820),
        ev(D, "jit__lambda(1)", 115, 165, "XLA Modules"),
        ev(D, "fusion.1", 125, 160, "XLA Ops"),
        ev(D, "jit_block(2)", 235, 425, "XLA Modules"),
        ev(D, "fusion.2", 240, 320, "XLA Ops"),
        ev(D, "fusion.3", 340, 420, "XLA Ops"),
    ]


def program_spans():
    return [
        # block 1: admission, then the decode block and its unpack
        ev(H, "repro.begin_tick", 100, 200),
        ev(H, "repro.admit", 110, 190),
        ev(H, "repro.prefill_oneshot", 120, 130),
        ev(H, "repro.splice", 130, 150),
        ev(H, "repro.first_token", 150, 180),
        ev(H, "repro.block_prep", 200, 230),
        ev(H, "repro.decode_block", 230, 450),
        ev(H, "repro.device_sync", 300, 450),
        ev(H, "repro.post_block", 460, 560),
        # block 2: admission finds nothing live to decode
        ev(H, "repro.begin_tick", 600, 650),
        ev(H, "repro.admit", 610, 640),
    ]


# idle: 0-125, 160-240, 320-340, 420-1000 us, 805 us of a 1000 us window
def test_idle_goes_to_every_program_span_over_it():
    r = spans.reduce_events(harness_and_device() + program_spans())
    assert r.window_s == pytest.approx(1000e-6)
    us = {n: t * 1e6 for n, t in r.by_span(top=20)}
    want = {
        # 100-125 and 160-200 in block 1, 600-650 in block 2
        "repro.begin_tick": 115, "repro.admit": 75,
        "repro.prefill_oneshot": 5, "repro.splice": 0, "repro.first_token": 20,
        "repro.block_prep": 30, "repro.decode_block": 60, "repro.device_sync": 50,
        "repro.post_block": 100,
        # pieces under no program span: 450-460, 560-600, 650-700
        "bench.step_block": 100, "bench.submit": 20,
        spans.NO_REQUEST: 380,
    }
    assert us == pytest.approx({k: v for k, v in want.items() if v})
    assert sum(r.pieces.values()) == pytest.approx(805e-6)


def test_a_gap_across_blocks_is_split_at_each_boundary():
    # a window holding only the gap 420-1000, which straddles device_sync,
    # post_block and the next block's begin_tick: each span gets the piece
    # under it, the rest goes to the harness span or to no request in flight
    trace = [e for e in harness_and_device() if e.name != "bench.window"]
    r = spans.reduce_events(trace + [ev(H, "bench.window", 420, 1000)] + program_spans())
    got = {tuple(sorted(k)): t * 1e6 for k, t in r.pieces.items()}
    sb = "bench.step_block"
    assert got == pytest.approx({
        (sb, "repro.decode_block", "repro.device_sync"): 30,
        (sb,): 10 + 40 + 50,
        (sb, "repro.post_block"): 100,
        (sb, "repro.begin_tick"): 10 + 10,
        (sb, "repro.admit", "repro.begin_tick"): 30,
        ("bench.submit",): 20,
        (): 100 + 180,
    })


def test_span_metrics_hand_worked():
    r = spans.reduce_events(harness_and_device() + program_spans())
    m = r.metrics()
    assert m["idle_admit_pct"] == pytest.approx(7.5)
    # block_prep 30 us, and decode_block outside device_sync 10 us
    assert m["idle_dispatch_pct"] == pytest.approx(4.0)
    assert m["idle_post_block_pct"] == pytest.approx(10.0)
    assert r.program_share() == pytest.approx(305 / 405)


def test_no_program_spans_reads_none():
    r = spans.reduce_events(harness_and_device())
    assert not r.traced
    assert set(r.metrics().values()) == {None}
    assert r.program_share() is None
    assert dict(r.by_span())["bench.step_block"] == pytest.approx(405e-6)
    with pytest.raises(ValueError):
        spans.reduce_events(program_spans())


def test_devtrace_reduction_ignores_program_spans():
    programs = {"prefill": r"^jit__lambda", "decode": r"^jit_block"}
    bare = devtrace.reduce_events(harness_and_device(), programs)
    full = devtrace.reduce_events(harness_and_device() + program_spans(), programs)
    assert (full.busy_s, full.program_s, full.program_runs, full.top_ops, full.gaps) == \
        (bare.busy_s, bare.program_s, bare.program_runs, bare.top_ops, bare.gaps)


def test_load_keeps_harness_and_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("repro.admit", uid=3):
                jnp.ones(4).block_until_ready()
            with TraceAnnotation("other"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = [e.name for e in spans.load_events(str(path)) if not e.plane.startswith("/device:")]
    assert sorted(names) == ["bench.window", "repro.admit"]


def test_span_idle_run_reports_the_split():
    """The tool's traced run at smoke size on the CPU: the server's spans
    reach the trace and hold nearly all the idle inside bench.step_block."""
    from bench import span_idle
    from conftest import smoke_cell

    out = span_idle.run(smoke_cell("lstm-decode-heavy"), 2**31 + 17, 1.0,
                        require_tpu=False, log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    s = out["spans"]
    assert {n for n, _ in s["idle_by_span"]} >= {"repro.admit", "repro.decode_block"}
    assert all(v is not None for v in s["metrics"].values())
    assert s["program_share_of_step_block"] > 0.9
