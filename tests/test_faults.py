"""PR 8 robustness tests: deadlines & cancellation, seeded fault injection,
slot quarantine + backend fallback, load shedding, and the stall watchdog.

The serving-side tests drive a real smoke-config model through the same
DecodeServer/AsyncServer APIs production would use; the chaos regression
asserts the acceptance contract — under every injected fault the affected
request retires with a structured ``finish_reason`` while the survivors'
token streams stay bit-identical to a fault-free run.
"""

import asyncio
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.runtime import (
    AsyncServer,
    DecodeServer,
    FAULT_POINTS,
    FaultPlan,
    FaultSpec,
    Request,
    Scheduler,
    SchedulerConfig,
    TransientFault,
    Watchdog,
)
from repro.runtime import faults as fl


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm-135m")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _requests(vocab, n=4, max_new=5, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=[int(t) for t in rng.integers(1, vocab, 5)],
                    max_new_tokens=max_new, **kw)
            for i in range(n)]


def _server(cfg, params, **kw):
    return DecodeServer(cfg, params, num_slots=kw.pop("slots", 4),
                        max_seq=kw.pop("max_seq", 64), **kw)


# ---------------------------------------------------------------------------
# FaultPlan semantics (pure unit tests)
# ---------------------------------------------------------------------------

def test_fault_spec_rejects_unknown_point():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultSpec("decode.never_heard_of_it")


def test_fault_plan_after_times_window():
    plan = FaultPlan([FaultSpec("tick.slow", after=2, times=2)], seed=0)
    fired = [plan.fire("tick.slow") is not None for _ in range(6)]
    assert fired == [False, False, True, True, False, False]
    rep = plan.report()
    assert rep["points"]["tick.slow"] == {"opportunities": 6, "fires": 2}
    assert plan.hits == {"tick.slow": 2}


def test_fault_plan_prob_is_seeded():
    def draws(seed):
        plan = FaultPlan([FaultSpec("tick.slow", prob=0.5, times=None)],
                         seed=seed)
        return [plan.fire("tick.slow") is not None for _ in range(32)]

    assert draws(7) == draws(7)          # replayable
    assert any(draws(7)) and not all(draws(7))
    assert draws(7) != draws(8)          # and actually seed-dependent


def test_fault_plan_maybe_raise_and_ambient_scope():
    plan = FaultPlan([FaultSpec("decode.dispatch")], seed=0)
    assert fl.get_plan() is None
    with fl.active(plan):
        assert fl.get_plan() is plan
        with pytest.raises(TransientFault):
            fl.maybe_raise("decode.dispatch")
        assert fl.fire("decode.dispatch") is None   # times=1 exhausted
    assert fl.get_plan() is None
    # no ambient plan: fire() is a no-op, never raises
    assert fl.fire("decode.dispatch") is None
    fl.maybe_raise("decode.dispatch")


def test_watchdog_bounds():
    with pytest.raises(ValueError):
        Watchdog(0.0)
    w = Watchdog(0.5, now=0.0)
    assert not w.stalled(0.4)
    assert w.stalled(0.6)
    w.progress(1.0)
    assert not w.stalled(1.4)
    assert w.idle_s(1.25) == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# Deadlines: submit / queued / mid-decode, both drivers
# ---------------------------------------------------------------------------

def test_deadline_zero_expires_at_submit(smollm):
    cfg, params = smollm
    srv = _server(cfg, params)
    req = _requests(cfg.vocab, 1, deadline_s=0.0)[0]
    assert srv.submit(req) is False
    assert req.finish_reason == "expired:queue"
    assert req.submitted_at is not None and req.retired_at is not None
    assert srv.completed == [req]


def test_deadline_none_never_expires(smollm):
    cfg, params = smollm
    srv = _server(cfg, params)
    for r in _requests(cfg.vocab, 2, deadline_s=None):
        assert srv.submit(r)
    done = srv.run_until_drained()
    assert all(r.finish_reason in ("eos", "max_tokens") for r in done)


def test_deadline_expires_while_queued(smollm):
    cfg, params = smollm
    srv = _server(cfg, params, slots=1)
    head = _requests(cfg.vocab, 1, max_new=4)[0]
    tail = _requests(cfg.vocab, 3, seed=1, deadline_s=0.01)
    for i, r in enumerate(tail):
        r.uid = 10 + i
    srv.submit(head)
    for r in tail:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == 4
    assert head.finish_reason in ("eos", "max_tokens")
    # the first tick's jit compile dwarfs the 10ms TTL: all queued expire
    assert all(r.finish_reason == "expired:queue" for r in tail)
    assert all(r.retired_at is not None for r in tail)


@pytest.mark.parametrize("persistent", [False, True])
def test_deadline_expires_mid_decode(smollm, persistent):
    cfg, params = smollm
    srv = _server(cfg, params, persistent=persistent, block_k=4)
    req = _requests(cfg.vocab, 1, max_new=500, deadline_s=0.2)[0]
    srv.submit(req)
    done = srv.run_until_drained()
    assert done == [req]
    assert req.finish_reason == "expired:decode"
    assert len(req.out_tokens) >= 1          # prefill-sampled first token
    assert req.retired_at is not None and req.retired_at >= req.deadline_at


def test_deadline_freed_slot_reused(smollm):
    cfg, params = smollm
    srv = _server(cfg, params, slots=1)
    doomed = _requests(cfg.vocab, 1, max_new=500, deadline_s=0.15)[0]
    follower = _requests(cfg.vocab, 1, seed=3, max_new=3)[0]
    follower.uid = 42
    srv.submit(doomed)
    srv.submit(follower)
    done = srv.run_until_drained()
    assert {r.uid for r in done} == {0, 42}
    assert doomed.finish_reason == "expired:decode"
    assert follower.finish_reason in ("eos", "max_tokens")


# ---------------------------------------------------------------------------
# Cancellation: server-level and asyncio front-end
# ---------------------------------------------------------------------------

def test_server_cancel_queued_and_live(smollm):
    cfg, params = smollm
    srv = _server(cfg, params, slots=1)
    first, second = _requests(cfg.vocab, 2, max_new=100)
    srv.submit(first)
    srv.submit(second)
    srv.step()                              # first live, second queued
    assert srv.cancel(second.uid) is True
    assert second.finish_reason == "cancelled"
    assert srv.cancel(first.uid) is True
    assert first.finish_reason == "cancelled"
    assert srv.cancel(999) is False
    assert srv.run_until_drained() == [second, first]
    assert all(r.retired_at is not None for r in (first, second))


def test_async_cancel_and_await_cancellation(smollm):
    cfg, params = smollm

    async def inner():
        # deep cache: neither request may retire via out_of_cache before
        # the cancel lands
        srv = _server(cfg, params, slots=2, max_seq=2048)
        a = AsyncServer(srv)
        victim = _requests(cfg.vocab, 1, max_new=500)[0]
        task = asyncio.ensure_future(a.generate(victim))
        await asyncio.sleep(0.05)           # let it go live
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert victim.finish_reason == "cancelled"

        # explicit cancel(): the awaiting generate() resolves normally
        second = _requests(cfg.vocab, 1, seed=2, max_new=500)[0]
        second.uid = 7
        task = asyncio.ensure_future(a.generate(second))
        await asyncio.sleep(0.05)
        assert a.cancel(7) is True
        out = await task
        assert out is second and out.finish_reason == "cancelled"

    asyncio.run(inner())


def test_async_duplicate_uid_fails_fast(smollm):
    cfg, params = smollm

    async def inner():
        srv = _server(cfg, params)
        a = AsyncServer(srv)
        first = _requests(cfg.vocab, 1, max_new=4)[0]
        task = asyncio.ensure_future(a.generate(first))
        await asyncio.sleep(0)              # first registers its future
        dup = _requests(cfg.vocab, 1, seed=5, max_new=4)[0]
        out = await a.generate(dup)         # same uid=0
        assert out is dup
        assert out.finish_reason == "rejected:duplicate_uid"
        assert out.submitted_at is not None and out.retired_at is not None
        # the original caller is unaffected by the duplicate
        done = await task
        assert done is first
        assert done.finish_reason in ("eos", "max_tokens")

    asyncio.run(inner())


def test_server_duplicate_uid_rejected(smollm):
    cfg, params = smollm
    srv = _server(cfg, params, slots=1)
    first, dup = _requests(cfg.vocab, 2, max_new=100)
    dup.uid = first.uid
    assert srv.submit(first) is True
    assert srv.submit(dup) is False
    assert dup.finish_reason == "rejected:duplicate_uid"
    assert dup.retired_at is not None
    srv.cancel(first.uid)
    assert srv.run_until_drained() == [dup, first]


# ---------------------------------------------------------------------------
# Quarantine: chaos regression — survivors bit-identical, slot reused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("persistent,point", [
    (False, "decode.nan_logits"),
    (False, "decode.nan_carry"),
    (True, "decode.nan_carry"),
])
def test_quarantine_survivors_bit_identical(smollm, persistent, point):
    cfg, params = smollm

    def run(plan):
        srv = _server(cfg, params, persistent=persistent, block_k=4,
                      faults=plan)
        for r in _requests(cfg.vocab, 4, max_new=6):
            srv.submit(r)
        return srv, {r.uid: r for r in srv.run_until_drained()}

    _, clean = run(None)
    plan = FaultPlan([FaultSpec(point, after=1)], seed=0)
    srv, faulty = run(plan)
    assert plan.hits[point] == 1
    bad = [r for r in faulty.values() if r.finish_reason == "error:nonfinite"]
    assert len(bad) == 1
    for uid, r in faulty.items():
        if r.finish_reason != "error:nonfinite":
            assert r.out_tokens == clean[uid].out_tokens, f"uid {uid} diverged"
    assert srv.health()["status"] == "degraded"
    assert int(srv.obs.metrics.value("slots_quarantined")) == 1
    assert int(srv.obs.metrics.value("faults_injected", point=point)) == 1


def test_quarantined_slot_scrubbed_and_reused(smollm):
    cfg, params = smollm
    plan = FaultPlan([FaultSpec("decode.nan_logits", after=1,
                                payload={"slot": 0})], seed=0)
    srv = _server(cfg, params, slots=1, faults=plan)
    poisoned = _requests(cfg.vocab, 1, max_new=6)[0]
    srv.submit(poisoned)
    srv.run_until_drained()
    assert poisoned.finish_reason == "error:nonfinite"
    # the scrubbed slot serves the next request normally
    fresh = _requests(cfg.vocab, 1, seed=9, max_new=4)[0]
    fresh.uid = 1
    srv.submit(fresh)
    srv.run_until_drained()
    assert fresh.finish_reason in ("eos", "max_tokens")
    assert not srv.quarantined.any()


def test_prefix_splice_corruption_quarantined(smollm):
    cfg, params = smollm
    plan = FaultPlan([FaultSpec("prefix.splice")], seed=0)
    srv = _server(cfg, params, faults=plan, prefix_cache_bytes=64 << 20)
    first = _requests(cfg.vocab, 1, max_new=4)[0]
    srv.submit(first)
    srv.run_until_drained()
    again = _requests(cfg.vocab, 1, max_new=4)[0]   # same prompt -> full hit
    again.uid = 1
    srv.submit(again)
    srv.run_until_drained()
    assert again.prefix_hit_tokens == len(again.prompt)
    assert again.finish_reason == "error:nonfinite"
    assert plan.hits["prefix.splice"] == 1


# ---------------------------------------------------------------------------
# Transient dispatch faults + stall watchdog
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("persistent", [False, True])
def test_dispatch_transient_fault_retried(smollm, persistent):
    cfg, params = smollm
    plan = FaultPlan([FaultSpec("decode.dispatch", times=2)], seed=0)
    srv = _server(cfg, params, persistent=persistent, block_k=4, faults=plan)
    for r in _requests(cfg.vocab, 3, max_new=4):
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == 3
    assert all(r.finish_reason in ("eos", "max_tokens") for r in done)
    assert int(srv.obs.metrics.value("decode_dispatch_retries")) == 2


def test_watchdog_aborts_permanent_stall(smollm):
    cfg, params = smollm
    plan = FaultPlan([FaultSpec("decode.dispatch", times=None)], seed=0)
    srv = _server(cfg, params, faults=plan, watchdog_s=0.2)
    reqs = _requests(cfg.vocab, 3, max_new=50)
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    done = srv.run_until_drained()
    assert time.perf_counter() - t0 < 30.0   # bounded, never hangs
    assert len(done) == 3
    assert all(r.finish_reason == "error:stalled" for r in reqs)
    assert all(r.retired_at is not None for r in reqs)
    h = srv.health()
    assert h["status"] == "stalled" and h["stalled_events"] >= 1
    assert int(srv.obs.metrics.value("server_stalled")) >= 1


def test_slow_tick_is_latency_only(smollm):
    cfg, params = smollm
    plan = FaultPlan([FaultSpec("tick.slow", times=2, delay_s=0.02)], seed=0)
    srv = _server(cfg, params, faults=plan)
    for r in _requests(cfg.vocab, 2, max_new=3):
        srv.submit(r)
    done = srv.run_until_drained()
    assert plan.hits["tick.slow"] == 2
    assert all(r.finish_reason in ("eos", "max_tokens") for r in done)


def test_health_snapshot_in_stats(smollm):
    cfg, params = smollm
    srv = _server(cfg, params, watchdog_s=60.0)
    for r in _requests(cfg.vocab, 2, max_new=3):
        srv.submit(r)
    srv.run_until_drained()
    h = srv.stats()["health"]
    assert h["status"] == "ok"
    assert h["quarantined_slots"] == 0 and h["stalled_events"] == 0
    assert h["watchdog_s"] == 60.0 and h["last_progress_idle_s"] >= 0


# ---------------------------------------------------------------------------
# Load shedding (scheduler unit tests — no model needed)
# ---------------------------------------------------------------------------

def test_shed_unserviceable_deadline():
    sched = Scheduler(SchedulerConfig(shed=True))
    # establish the observed dispatch interval: 0.1 s/request
    for i in range(4):
        r = Request(uid=i, prompt=[1, 2], max_new_tokens=1)
        assert sched.admit(r, now=100.0)[0]
        sched.next_request(now=100.0 + 0.1 * i)
    for i in range(5):   # five pending ahead of the newcomer
        assert sched.admit(Request(uid=10 + i, prompt=[1], max_new_tokens=1),
                           now=100.4)[0]
    hopeless = Request(uid=50, prompt=[1], max_new_tokens=1, deadline_s=0.2)
    ok, reason = sched.admit(hopeless, now=100.4)
    assert (ok, reason) == (False, "shed")
    assert hopeless.finish_reason == "rejected:shed"
    roomy = Request(uid=51, prompt=[1], max_new_tokens=1, deadline_s=10.0)
    assert sched.admit(roomy, now=100.4)[0]


def test_shed_evicts_least_urgent_on_full_queue():
    sched = Scheduler(SchedulerConfig(shed=True, max_queue=2))
    bulk = [Request(uid=i, prompt=[1], max_new_tokens=1, priority=5)
            for i in range(2)]
    for r in bulk:
        assert sched.admit(r, now=0.0)[0]
    urgent = Request(uid=9, prompt=[1], max_new_tokens=1, priority=0)
    assert sched.admit(urgent, now=0.0)[0]
    victims = sched.drain_evicted()
    assert [v.uid for v in victims] == [1]   # youngest of the worst class
    assert victims[0].finish_reason == "rejected:shed"
    assert len(sched) == 2
    # a newcomer NOT more urgent than the worst queued is bounced instead
    meh = Request(uid=11, prompt=[1], max_new_tokens=1, priority=5)
    ok, reason = sched.admit(meh, now=0.0)
    assert (ok, reason) == (False, "queue_full")


def test_shed_victim_retired_by_server(smollm):
    cfg, params = smollm
    srv = _server(cfg, params, slots=1,
                  scheduler=SchedulerConfig(shed=True, max_queue=1))
    reqs = _requests(cfg.vocab, 2, max_new=100)
    reqs[1].priority = 5
    srv.submit(reqs[0])
    srv.step()                  # uid0 live; queue empty
    srv.submit(reqs[1])         # uid1 queued (priority 5), queue now full
    urgent = _requests(cfg.vocab, 1, seed=4, max_new=100)[0]
    urgent.uid, urgent.priority = 9, 0
    assert srv.submit(urgent)
    assert reqs[1].finish_reason == "rejected:shed"
    assert reqs[1].retired_at is not None
    assert reqs[1] in srv.completed
    for uid in (0, 9):
        srv.cancel(uid)
    srv.run_until_drained()


# ---------------------------------------------------------------------------
# Synthesis fallback chain + rtlsim SEU
# ---------------------------------------------------------------------------

def _tiny_spec(**kw):
    from repro.core.synthesis import NetworkSpec

    return NetworkSpec(num_inputs=4, num_hidden_layers=2, nodes_per_layer=8,
                       num_outputs=2, **kw)


def test_synth_transient_retry_succeeds():
    from repro.core.synthesis import synthesize, synthesize_cache_clear

    synthesize_cache_clear()
    plan = FaultPlan([FaultSpec("synth.compile", times=2)], seed=0)
    with fl.active(plan):
        rep = synthesize(_tiny_spec(), batch=2, backend="xla",
                         measure=False, backoff_s=0.0)
    assert rep.backend == "xla" and rep.fallback_from is None
    assert plan.hits["synth.compile"] == 2
    synthesize_cache_clear()


def test_synth_fallback_chain_to_ref():
    from repro.core.synthesis import synthesize, synthesize_cache_clear

    synthesize_cache_clear()
    plan = FaultPlan([FaultSpec("synth.compile", times=3)], seed=0)
    with fl.active(plan):
        rep = synthesize(_tiny_spec(), batch=2, backend="xla",
                         measure=False, backoff_s=0.0)
    assert rep.backend == "ref" and rep.fallback_from == "xla"
    assert rep.output_shape == (2, 2)
    synthesize_cache_clear()


def test_synth_fallback_disabled_raises():
    from repro.core.synthesis import synthesize, synthesize_cache_clear

    synthesize_cache_clear()
    plan = FaultPlan([FaultSpec("synth.compile", times=None)], seed=0)
    with fl.active(plan), pytest.raises(TransientFault):
        synthesize(_tiny_spec(), batch=2, backend="xla", measure=False,
                   backoff_s=0.0, fallback=False)
    synthesize_cache_clear()


def test_synth_non_transient_error_is_reraised(monkeypatch):
    """A compile error that is not a TransientFault (e.g. the device's
    compiler refusing a kernel) surfaces instead of degrading to xla."""
    from repro.core import synthesis
    from repro.core.synthesis import synthesize, synthesize_cache_clear

    def refuse(program, spec, backend, *a, **kw):
        raise RuntimeError(f"{backend}: scoped vmem exceeded")

    synthesize_cache_clear()
    monkeypatch.setattr(synthesis, "_build_fwd", refuse)
    with pytest.raises(RuntimeError, match="pallas: scoped vmem"):
        synthesize(_tiny_spec(), batch=2, backend="pallas", measure=False,
                   backoff_s=0.0)
    synthesize_cache_clear()


def test_synth_ref_backend_matches_xla():
    from repro.core.synthesis import synthesize, synthesize_cache_clear

    synthesize_cache_clear()
    a = synthesize(_tiny_spec(), batch=2, backend="xla", measure=False)
    b = synthesize(_tiny_spec(), batch=2, backend="ref", measure=False)
    assert a.output_shape == b.output_shape
    assert b.backend == "ref" and b.fallback_from is None
    synthesize_cache_clear()


def test_rtlsim_seu_flip_recorded_and_replayable():
    from repro import codegen

    prog = codegen.build_program(_tiny_spec(quant_bits=16))
    u = np.random.default_rng(0).uniform(-1, 1, (2, 4))
    clean = codegen.rtlsim.simulate(prog, u)
    assert clean.seu_flips == []

    def faulted():
        plan = FaultPlan([FaultSpec("rtlsim.seu", after=1)], seed=3)
        return codegen.rtlsim.simulate(prog, u, fault_plan=plan)

    hit, replay = faulted(), faulted()
    assert len(hit.seu_flips) == 1
    flip = hit.seu_flips[0]
    assert set(flip) == {"stream", "step", "stage", "state", "index", "bit"}
    assert not np.array_equal(clean.y_codes, hit.y_codes)
    assert np.array_equal(hit.y_codes, replay.y_codes)
    assert hit.seu_flips == replay.seu_flips
    # a later clean run is untouched (no lingering plan state)
    assert np.array_equal(clean.y_codes,
                          codegen.rtlsim.simulate(prog, u).y_codes)


def test_rtlsim_seu_payload_pins_target():
    from repro import codegen

    prog = codegen.build_program(_tiny_spec(quant_bits=16))
    u = np.zeros((1, 4))
    plan = FaultPlan([FaultSpec("rtlsim.seu",
                                payload={"stage": 0, "index": 0,
                                         "bit": 15})], seed=0)
    res = codegen.rtlsim.simulate(prog, u, fault_plan=plan)
    assert len(res.seu_flips) == 1
    flip = res.seu_flips[0]
    assert (flip["index"], flip["bit"], flip["stream"]) == (0, 15, 0)
    assert isinstance(flip["stage"], str) and isinstance(flip["state"], str)


# ---------------------------------------------------------------------------
# Chaos report schema (repro.obs.check)
# ---------------------------------------------------------------------------

def _chaos_doc():
    return {
        "schema": "repro.chaos/v1", "suite": "chaos", "seed": 0,
        "scenarios": [{"name": "s", "passed": True,
                       "faults": {"tick.slow": 1}, "detail": {}}],
        "fault_classes": {p: 1 for p in FAULT_POINTS},
        "all_classes_hit": True, "passed": True,
    }


def test_check_chaos_doc_accepts_valid():
    from repro.obs.check import check_chaos_doc

    assert check_chaos_doc(_chaos_doc()) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.update(schema="repro.chaos/v0"), "unknown schema"),
    (lambda d: d.update(scenarios=[]), "non-empty"),
    (lambda d: d["fault_classes"].pop("rtlsim.seu"), "never exercised"),
    (lambda d: d["fault_classes"].update({"rtlsim.seu": 0}), "zero fires"),
    (lambda d: d["scenarios"][0].update(passed=False), "scenario failed"),
    (lambda d: d.update(all_classes_hit=False), "all_classes_hit"),
])
def test_check_chaos_doc_rejects_broken(mutate, needle):
    from repro.obs.check import check_chaos_doc

    doc = _chaos_doc()
    mutate(doc)
    errs = check_chaos_doc(doc)
    assert errs and any(needle in e for e in errs), errs
