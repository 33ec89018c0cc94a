"""Observability-layer tests: metrics registry, span tracing, leveled log,
predicted-vs-measured ledger, and the serving telemetry wired through them.

The serving assertions are *exact-count* tests on a fully deterministic
workload (greedy decode, fixed prompts, single slot where needed): the
telemetry IS the acceptance contract of PRs 3-4 (sync reduction, bounded
per-tick prompt work, zero recomputation on full prefix hits), so the
numbers are asserted, not just their signs.
"""

import json
import threading

import jax
import numpy as np
import pytest

from repro import obs as obs_lib
from repro.configs import get_smoke_config
from repro.models import lm
from repro.obs import log
from repro.obs.check import check_metrics_doc, check_trace_doc
from repro.obs.ledger import Ledger
from repro.obs.log import fmt_or_na
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.runtime import DecodeServer, Request


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basic():
    m = MetricsRegistry()
    c = m.counter("reqs", "requests", route="decode")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # same (name, labels) -> same child; different labels -> sibling
    assert m.counter("reqs", route="decode") is c
    other = m.counter("reqs", route="prefill")
    assert other is not c and other.value == 0
    assert m.value("reqs", route="decode") == 5
    assert {ch.labels["route"] for ch in m.children("reqs")} == \
        {"decode", "prefill"}
    g = m.gauge("depth")
    g.set(3)
    g.set_max(1)    # lower: no change
    g.set_max(7)
    assert g.value == 7
    g.add(-2)
    assert g.value == 5


def test_kind_collision_rejected():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("x")


def test_histogram_percentiles_exact():
    m = MetricsRegistry()
    h = m.histogram("lat_ms")
    for v in range(1, 101):           # 1..100, under the reservoir size
        h.observe(v)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    assert s["sum"] == pytest.approx(5050)
    # nearest-rank on the full population
    assert s["p50"] == 50 and s["p95"] == 95 and s["p99"] == 99
    assert m.histogram("empty").summary()["p50"] is None


def test_registry_reset_keeps_families():
    m = MetricsRegistry()
    c = m.counter("n")
    h = m.histogram("d")
    c.inc(3)
    h.observe(1.0)
    m.reset()
    assert c.value == 0 and h.summary()["count"] == 0
    # the SAME handles keep working after reset (hot-path handle caching)
    c.inc()
    assert m.value("n") == 1


def test_snapshot_and_prometheus():
    m = MetricsRegistry()
    m.counter("hits", "cache hits", kind="full").inc(2)
    m.gauge("depth").set(4)
    m.histogram("ms").observe(10.0)
    snap = m.snapshot()
    assert snap["counters"]["hits{kind=full}"] == 2
    assert snap["gauges"]["depth"] == 4
    assert snap["histograms"]["ms"]["count"] == 1
    text = m.to_prometheus()
    assert '# TYPE hits counter' in text
    assert 'hits{kind="full"} 2' in text
    assert "# TYPE ms summary" in text
    assert "ms_count 1" in text
    json.loads(m.to_json())           # valid JSON


def test_registry_thread_safety():
    m = MetricsRegistry()
    c = m.counter("n")
    h = m.histogram("v")

    def work():
        for i in range(1000):
            c.inc()
            h.observe(i)

    ts = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 8000
    assert h.summary()["count"] == 8000


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_is_null():
    tr = Tracer(enabled=False)
    span = tr.span("x")
    assert span is tr.span("y")       # one shared null context manager
    with span:
        pass
    tr.instant("i")
    tr.counter("c", {"v": 1})
    tr.thread_name(0, "server")
    assert tr.events() == []


def test_tracer_spans_and_schema(tmp_path):
    tr = Tracer(enabled=True)
    tr.thread_name(0, "server")
    with tr.span("outer", cat="test", args={"k": 1}):
        with tr.span("inner", cat="test"):
            pass
    tr.instant("mark")
    by_name = {e["name"]: e for e in tr.events()}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == "X" and inner["ph"] == "X"
    # nesting by timestamp containment on the same track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"k": 1}
    assert by_name["thread_name"]["ph"] == "M"
    path = tmp_path / "trace.json"
    doc = tr.export(str(path))
    assert doc["traceEvents"] and json.load(open(path)) == doc
    assert check_trace_doc(doc) == []
    tr.reset()
    assert tr.events() == []


def test_trace_doc_schema_rejects_malformed():
    assert check_trace_doc({"nope": 1})
    assert check_trace_doc({"traceEvents": [{"ph": "X"}]})  # missing fields


# ---------------------------------------------------------------------------
# log levels (satellite: REPRO_LOG + dryrun flops=None rendering)
# ---------------------------------------------------------------------------

def test_log_levels(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_LOG", "info")
    log.info("hello", n=3)
    log.debug("hidden")
    out = capsys.readouterr().out
    assert out == "hello n=3\n"
    monkeypatch.setenv("REPRO_LOG", "debug")
    log.debug("shown")
    assert "[debug] shown" in capsys.readouterr().out
    monkeypatch.setenv("REPRO_LOG", "quiet")
    log.info("silent")
    log.warning("silent too")
    got = capsys.readouterr()
    assert got.out == "" and got.err == ""


def test_fmt_or_na():
    # the dryrun crash: f"...{None:.3e}" raised; fmt_or_na renders 'n/a'
    assert fmt_or_na(None) == "n/a"
    assert fmt_or_na("n/a") == "n/a"
    assert fmt_or_na(True) == "n/a"
    assert fmt_or_na(12345.0) == "1.234e+04"
    assert fmt_or_na(7, "{:d}") == "7"


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------

def test_ledger_join_and_derived_columns():
    led = Ledger()
    led.predict("prog|xla|u1|c1", fsm_cycles=1000, flops=2e6, peak_bytes=None)
    led.measure("prog|xla|u1|c1", wall_s=2e-3)
    led.measure("prog|xla|u1|c1", wall_s=1e-3)     # best-of wins
    led.predict("other", fsm_cycles=5)             # predicted-only row
    rows = {r["program"]: r for r in led.report()}
    r = rows["prog|xla|u1|c1"]
    assert r["fsm_cycles"] == 1000 and r["measured_calls"] == 2
    assert r["measured_wall_us"] == pytest.approx(1000.0)
    assert "peak_bytes" not in r["predicted"]      # None dropped
    # implied clock: cycles / wall_us -> 1000 cycles in 1000us = 1 MHz
    assert r["implied_clock_mhz"] == pytest.approx(1.0)
    assert r["measured_gflops"] == pytest.approx(2e6 / 1e-3 / 1e9)
    assert rows["other"]["measured_wall_us"] is None
    table = led.format_table()
    assert "prog|xla|u1|c1" in table and "n/a" in table
    led.reset()
    assert led.format_table().startswith("(ledger empty")


def test_synthesize_populates_ledger_and_cache_counter():
    from repro.core.synthesis import NetworkSpec, synthesize

    O = obs_lib.OBS
    spec = NetworkSpec(3, 1, 4, 2, cell="gru", seq_len=5, unroll=1, c_slow=1)
    hits0 = O.metrics.value("synth_cache", result="hit")
    rep = synthesize(spec, batch=2, backend="xla")
    row = {r["program"]: r for r in O.ledger.report()}.get(
        f"{spec.name}|xla|u1|c1|b2")
    assert row is not None
    assert row["fsm_cycles"] and row["fsm_cycles"] > 0
    assert row["flops"] == rep.flops
    assert row["measured_calls"] >= 1 and row["measured_wall_us"] > 0
    assert "implied_clock_mhz" in row
    synthesize(spec, batch=2, backend="xla")       # memoized
    assert O.metrics.value("synth_cache", result="hit") == hits0 + 1


# ---------------------------------------------------------------------------
# serving telemetry: exact counts on a deterministic workload
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm-135m")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 40, size=n)]


def test_server_exact_telemetry_and_trace(smollm):
    """Chunked prefill + prefix cache + persistent decode, tracing on:
    every acceptance counter is asserted to its exact value."""
    cfg, params = smollm
    O = obs_lib.Observability(trace=True)
    srv = DecodeServer(cfg, params, num_slots=1, max_seq=64,
                       persistent=True, block_k=4, prefill_chunk=4,
                       prefix_cache_bytes=64 << 20, obs=O)
    prompt = _prompt(8)

    srv.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=6))
    srv.run_until_drained()
    s = srv.stats()
    # prefill: 8 prompt tokens in 2 chunks of 4; bounded by the chunk
    assert s["prefill"]["prompt_steps_computed"] == 8
    assert s["prefill"]["chunks_run"] == 2
    assert s["prefill"]["max_prompt_steps_per_tick"] == 4
    # decode: first token from prefill logits, 5 device-decoded in blocks of
    # 4 -> ceil(5/4) = 2 block dispatches = 2 host syncs
    assert s["decoded_tokens"] == 5
    assert s["decode_syncs"] == 2
    assert s["syncs_per_token"] == pytest.approx(2 / 5)
    pc = s["prefix_cache"]
    assert pc["misses"] == 1 and pc["hits"] == 0
    assert pc["insertions"] == 2          # chunk boundary @4 + prompt end @8
    assert pc["prompt_steps_saved"] == 0

    # same prompt again: full hit -> ZERO recomputed prompt steps
    srv.submit(Request(uid=1, prompt=list(prompt), max_new_tokens=6))
    srv.run_until_drained()
    s = srv.stats()
    assert s["prefill"]["prompt_steps_computed"] == 8      # unchanged
    assert s["prefix_cache"]["hits"] == 1
    assert s["prefix_cache"]["prompt_steps_saved"] == 8
    assert s["decoded_tokens"] == 10 and s["decode_syncs"] == 4
    assert s["scheduler"]["dispatched"] == 2
    lat = s["latency"]
    assert lat["ttft_ms"]["count"] == 2 and lat["ttft_ms"]["p95"] > 0
    assert lat["queue_wait_ms"]["count"] == 2
    assert lat["tpot_ms"]["count"] == 2

    # trace: schema-valid; per-request spans nest by timestamp containment
    doc = O.export_trace()
    assert check_trace_doc(doc) == []
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"decode_block", "device_sync", "prefill_chunk", "request",
            "queue_wait", "prefill", "decode", "thread_name"} <= names
    for uid in (0, 1):
        tid = uid + 1
        track = [e for e in evs if e["tid"] == tid and e["ph"] == "X"]
        parent = next(e for e in track if e["name"] == "request")
        children = [e for e in track if e["name"] != "request"]
        assert {"queue_wait", "prefill", "decode"} == \
            {e["name"] for e in children}
        for ch in children:
            assert ch["ts"] >= parent["ts"] - 1e-6
            assert ch["ts"] + ch["dur"] <= parent["ts"] + parent["dur"] + 1e-6
    # request 1 was a full cache hit: its prefill span carries no chunks
    # (all prefill_chunk spans live on the server track, and there are
    # exactly 2 — request 0's)
    assert sum(e["name"] == "prefill_chunk" for e in evs) == 2
    # metrics document cross-check: exported snapshot == stats() numbers
    mdoc = O.export_metrics(stats=s)
    assert check_metrics_doc(mdoc) == []
    assert mdoc["metrics"]["counters"]["decoded_tokens"] == s["decoded_tokens"]

    # stats(reset=True): next window starts at zero, cache entries survive
    srv.stats(reset=True)
    s = srv.stats()
    assert s["decoded_tokens"] == 0 and s["decode_syncs"] == 0
    assert s["prefix_cache"]["entries"] == 2      # checkpoints untouched


def test_partial_then_full_hit_accounting(smollm):
    """The prefix-cache audit regression test: a partial hit followed by a
    full hit of the same prompt saves start + plen in total — one decision
    per admission, never a double count.  Invariant checked against ground
    truth: computed + saved == total prompt tokens submitted."""
    cfg, params = smollm
    srv = DecodeServer(cfg, params, num_slots=1, max_seq=64,
                       prefill_chunk=4, prefix_cache_bytes=64 << 20)
    head = _prompt(4, seed=1)
    tail_a = _prompt(4, seed=2)
    tail_b = _prompt(4, seed=3)
    prompts = [head + tail_a,      # cold: miss, computes 8, inserts @4 @8
               head + tail_b,      # partial hit @4: computes 4, inserts @8
               head + tail_b]      # full hit: computes 0
    for uid, p in enumerate(prompts):
        srv.submit(Request(uid=uid, prompt=list(p), max_new_tokens=2))
        srv.run_until_drained()
    pc = srv.stats()["prefix_cache"]
    assert pc["misses"] == 1
    assert pc["partial_hits"] == 1
    assert pc["hits"] == 1
    assert pc["prompt_steps_saved"] == 4 + 8       # partial start + full plen
    computed = srv.stats()["prefill"]["prompt_steps_computed"]
    assert computed == 8 + 4 + 0
    assert computed + pc["prompt_steps_saved"] == sum(map(len, prompts))


def test_rejection_metrics(smollm):
    cfg, params = smollm
    srv = DecodeServer(cfg, params, num_slots=1, max_seq=16)
    assert not srv.submit(Request(uid=0, prompt=[], max_new_tokens=2))
    s = srv.stats()
    assert s["scheduler"]["rejected"] == {"empty_prompt": 1}
    assert srv.obs.metrics.value("requests_completed", reason="rejected") == 1
    assert srv.completed[0].finish_reason == "rejected:empty_prompt"


def test_server_tracing_disabled_by_default(smollm):
    cfg, params = smollm
    srv = DecodeServer(cfg, params, num_slots=1, max_seq=32)
    srv.submit(Request(uid=0, prompt=_prompt(3), max_new_tokens=2))
    srv.run_until_drained()
    assert srv.obs.tracer.events() == []
    assert srv.stats()["decoded_tokens"] == 1


# the persistent path's spans, as the server opens them
STEP_BLOCK_SPANS = ("begin_tick", "admit", "prefill_oneshot", "splice",
                    "first_token", "block_prep", "decode_block",
                    "device_sync", "post_block")


def _profiled_spans(smollm, log_dir, trace: bool) -> list[dict]:
    """Serve two requests on a persistent server under a JAX profiler
    session; the ``repro.*`` host events of the resulting ``.xplane.pb``."""
    from jax.profiler import ProfileData

    cfg, params = smollm
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=32, persistent=True,
                       block_k=2, obs=obs_lib.Observability(trace=trace))
    for uid in (0, 1):
        srv.submit(Request(uid=uid, prompt=_prompt(3, seed=uid),
                           max_new_tokens=4))
    jax.profiler.start_trace(str(log_dir))
    try:
        srv.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    assert srv.stats()["decoded_tokens"] == 6
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out += [{"plane": plane.name, "line": line.name,
                     "name": e.name[len("repro."):], "start": e.start_ns,
                     "end": e.start_ns + e.duration_ns, "args": dict(e.stats)}
                    for e in line.events if e.name.startswith("repro.")]
    return out


def _inside(inner: dict, outer: dict) -> bool:
    return (inner["line"] == outer["line"] and outer["start"] <= inner["start"]
            and inner["end"] <= outer["end"])


def test_enabled_spans_reach_the_profiler_trace(smollm, tmp_path):
    evs = _profiled_spans(smollm, tmp_path, trace=True)
    assert {e["name"] for e in evs} >= set(STEP_BLOCK_SPANS)
    assert all(e["plane"].startswith("/host:") for e in evs)
    by = {n: [e for e in evs if e["name"] == n] for n in STEP_BLOCK_SPANS}
    for name in ("prefill_oneshot", "splice", "first_token"):
        for e in by[name]:
            assert any(_inside(e, a) for a in by["admit"]), name
            assert any(_inside(e, t) for t in by["begin_tick"]), name
    for e in by["device_sync"]:
        assert any(_inside(e, d) for d in by["decode_block"])
    # the spans of one request share its uid: one admission each
    for name in ("prefill_oneshot", "splice", "first_token"):
        assert sorted(e["args"]["uid"] for e in by[name]) == [0, 1], name
    assert all(e["args"]["k"] == 2 for e in by["decode_block"])
    assert sum(e["args"]["emitted"] for e in by["post_block"]) == 6


def test_disabled_tracer_writes_no_profiler_spans(smollm, tmp_path):
    assert _profiled_spans(smollm, tmp_path, trace=False) == []


# ---------------------------------------------------------------------------
# perf-suite regression gate (satellite: p95 gate for serve_mixed_*)
# ---------------------------------------------------------------------------

def test_perf_check_gates_ttft_p95():
    from benchmarks.perf_suite import TTFT_P95_FACTOR, check

    def payload(p95):
        return {"smoke": True, "records": [
            {"bench": "serve_mixed_chunked", "syncs_per_token": 0.5,
             "ttft_p95_ms": p95, "tick_bound_ok": True,
             "greedy_identical": True}]}

    committed = payload(100.0)
    assert check(payload(100.0 * TTFT_P95_FACTOR * 0.9), committed) == []
    bad = check(payload(100.0 * TTFT_P95_FACTOR * 1.1), committed)
    assert bad and "ttft_p95_ms" in bad[0]
    # different workload (smoke flags differ): wall-clock gate is skipped
    fresh = payload(100.0 * TTFT_P95_FACTOR * 10)
    fresh["smoke"] = False
    assert check(fresh, committed) == []
