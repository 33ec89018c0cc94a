"""Trace reduction on a synthetic trace worked by hand."""

import pytest

from bench.devtrace import Event, reduce_events, union_ns

D, H = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, s_us, e_us):
    return Event(plane, line, name, s_us * 1e3, (e_us - s_us) * 1e3)


def test_union():
    assert union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def trace():
    return [
        ev(H, "t", "bench.window", 100, 1100),
        ev(H, "t", "bench.step_block", 100, 700),
        ev(H, "t", "bench.submit", 800, 900),
        # a prefill program with a kernel inside, and a decode block
        ev(D, "XLA Modules", "jit__lambda(1)", 150, 350),
        ev(D, "XLA Ops", "stage_kernel = fusion(...), kind=kCustom", 160, 300),
        ev(D, "XLA Ops", "fusion.1", 300, 350),
        ev(D, "XLA Modules", "jit_block(2)", 400, 600),
        ev(D, "XLA Ops", "fusion.2", 400, 500),
        ev(D, "XLA Ops", "fusion.2", 480, 600),        # overlaps the one before
        ev(D, "XLA Ops", "fusion.3", 50, 120),         # starts before the window
        ev(D, "XLA Ops", "fusion.4", 1000, 1200),      # ends after it
    ]


def test_busy_idle_and_programs():
    r = reduce_events(trace(), {"prefill": r"^jit__lambda", "decode": r"^jit_block",
                                "stage_kernel": r"^stage_kernel = .*kind=kCustom"})
    assert r.window_s == pytest.approx(1000e-6)
    # busy: 100-120, 160-350, 400-600, 1000-1100 -> 20 + 190 + 200 + 100 us
    assert r.busy_s == pytest.approx(510e-6)
    assert r.idle_pct == pytest.approx(49.0)
    assert r.program_s["prefill"] == pytest.approx(200e-6)
    assert r.program_s["decode"] == pytest.approx(200e-6)
    assert r.program_s["stage_kernel"] == pytest.approx(140e-6)
    assert r.program_runs == {"prefill": 1, "decode": 1, "stage_kernel": 1}
    ops = dict(r.top_ops)
    assert ops["jit_block/fusion.2"] == pytest.approx(200e-6)      # union of its two runs
    assert ops["jit__lambda/stage_kernel"] == pytest.approx(140e-6)
    assert ops["(no module)/fusion.4"] == pytest.approx(100e-6)


def test_idle_gaps_are_labelled_by_host_spans():
    r = reduce_events(trace(), {})
    # 600-1000 us: mid 800 lies in bench.submit; 120-160 and 350-400 in
    # bench.step_block
    assert r.gaps[0] == ("total, host: bench.submit, 1 gaps", pytest.approx(400e-6))
    assert r.gaps[1] == ("total, host: bench.step_block, 2 gaps", pytest.approx(90e-6))
    assert [n for n, _ in r.gaps[2:]] == ["host: bench.submit", "host: bench.step_block",
                                          "host: bench.step_block"]
    assert len(r.breakdown()["idle_gaps"]) <= 10


def test_nested_ops_count_their_self_time_and_duplicates_once():
    from bench.devtrace import self_times

    evs = [ev(D, "XLA Ops", "while", 0, 100), ev(D, "XLA Ops", "body", 10, 40),
           ev(D, "XLA Ops", "body", 50, 60)]
    t = self_times(evs)
    assert t["while"] == pytest.approx(60e-6) and t["body"] == pytest.approx(40e-6)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        reduce_events([ev(D, "XLA Ops", "fusion.1", 0, 1)], {})
