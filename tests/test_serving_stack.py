"""PR 4 serving-stack tests: chunked prefill (resumable prompt scan), the
radix prefix cache, the priority/aging scheduler with admission control, and
the decode-server correctness fixes (max_new_tokens off-by-one, over-length
splice validation, cslow_scan length inference, sampled-sync accounting)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cslow import cslow_scan
from repro.core.state_space import StateSpaceModel
from repro.models import lm
from repro.runtime import (
    AsyncServer,
    DecodeServer,
    PrefixCache,
    Request,
    Scheduler,
    SchedulerConfig,
    splice_cache,
)


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm-135m")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _requests(vocab, n=5, max_new=6, seed=0, lo=2, hi=6):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=list(rng.integers(1, vocab, size=int(rng.integers(lo, hi)))),
                    max_new_tokens=max_new)
            for i in range(n)]


def _drain(cfg, params, reqs, **kw):
    srv = DecodeServer(cfg, params, num_slots=kw.pop("slots", 3),
                       max_seq=kw.pop("max_seq", 48), **kw)
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    return {r.uid: list(r.out_tokens) for r in done}, srv


# ---------------------------------------------------------------------------
# chunked prefill: resumable prompt scan ≡ one-shot prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-135m", "falcon-mamba-7b",
                                  "zamba2-1.2b", "gemma3-27b", "paper-lstm"])
def test_prefill_chunk_matches_prefill(arch):
    """Chaining prefill_chunk from a fresh cache reproduces one-shot prefill
    (KV, MLA, sliding-window ring, SSM h/conv, and (h,c) states alike)."""
    cfg = get_smoke_config(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    T, S = 19, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, T), 1, cfg.vocab)
    lg_ref, _ = lm.prefill(params, cfg, toks)
    caches = lm.init_cache(cfg, 1, S)
    p = 0
    while p < T:
        c = min(8, T - p)
        lg, caches = lm.prefill_chunk(params, cfg, toks[:, p:p + c], caches,
                                      jnp.int32(p))
        p += c
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref), atol=1e-4)
    assert int(jnp.argmax(lg[0])) == int(jnp.argmax(lg_ref[0]))


def test_prefill_chunk_moe_greedy_parity():
    """MoE capacity-based routing drops tokens group-locally, so chunked
    logits are only approximately equal — but the greedy token matches (the
    same caveat the S=1 decode path already has)."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 19), 1, cfg.vocab)
    lg_ref, _ = lm.prefill(params, cfg, toks)
    caches = lm.init_cache(cfg, 1, 32)
    p = 0
    while p < 19:
        c = min(8, 19 - p)
        lg, caches = lm.prefill_chunk(params, cfg, toks[:, p:p + c], caches,
                                      jnp.int32(p))
        p += c
    assert int(jnp.argmax(lg[0])) == int(jnp.argmax(lg_ref[0]))


@pytest.mark.parametrize("arch", ["smollm-135m", "falcon-mamba-7b", "paper-lstm"])
def test_server_chunked_greedy_parity(arch):
    """Chunked-prefill serving emits token-identical greedy outputs to the
    un-chunked cache-cold path, for both decode drivers."""
    cfg = get_smoke_config(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    base, _ = _drain(cfg, params, _requests(cfg.vocab))
    chunked, srv = _drain(cfg, params, _requests(cfg.vocab), prefill_chunk=2)
    assert base == chunked
    persist, _ = _drain(cfg, params, _requests(cfg.vocab), prefill_chunk=2,
                        persistent=True, block_k=4)
    assert base == persist
    assert srv.stats()["prefill"]["max_prompt_steps_per_tick"] <= 2


def test_chunked_prefill_bounds_tick_and_unblocks_decode(smollm):
    """A long prompt no longer head-of-line-blocks a live slot: with
    chunking, short requests decode (and even finish) while the long prompt
    is still prefilling, and no single tick consumes the whole prompt."""
    cfg, params = smollm
    long_prompt = list(np.random.default_rng(7).integers(1, cfg.vocab, size=24))

    def traffic():
        short = _requests(cfg.vocab, n=1, max_new=4, seed=1)[0]
        longr = Request(uid=99, prompt=list(long_prompt), max_new_tokens=2)
        return [longr, short]

    # unchunked: the long prefill lands whole in a single tick
    _, s0 = _drain(cfg, params, traffic(), slots=2, max_seq=64)
    assert s0.stats()["prefill"]["max_prompt_steps_per_tick"] >= 24
    # chunked: per-tick prompt work is bounded by the chunk
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=64, prefill_chunk=4)
    longr = Request(uid=99, prompt=list(long_prompt), max_new_tokens=2)
    short = _requests(cfg.vocab, n=1, max_new=4, seed=1)[0]
    srv.submit(longr)
    srv.submit(short)
    # drive ticks manually: the short request must finish before the long
    # prompt's first token is out
    for _ in range(20):
        srv.step()
        if short.done_at is not None:
            break
    assert short.done_at is not None
    assert longr.first_token_at is None     # still prefilling
    srv.run_until_drained()
    st = srv.stats()["prefill"]
    assert st["max_prompt_steps_per_tick"] <= 4
    assert len(longr.out_tokens) == 2


def test_adaptive_prefill_bounds_contended_ticks_only(smollm):
    """Adaptive chunking: prefill arriving on an idle server drains whole
    (no fixed-chunk dispatch tax), but the chunk bound still holds on every
    tick where a live slot is decoding — and greedy outputs stay identical
    to the unchunked run."""
    cfg, params = smollm
    long_prompt = list(np.random.default_rng(7).integers(1, cfg.vocab, size=24))

    def traffic():
        longr = Request(uid=99, prompt=list(long_prompt), max_new_tokens=2)
        return [longr] + _requests(cfg.vocab, n=1, max_new=4, seed=1)

    base, _ = _drain(cfg, params, traffic(), slots=2, max_seq=64)

    # all traffic fits the slots up-front → the first tick is uncontended
    # and drains whole prompts; nothing ever prefills under contention
    ad, srv = _drain(cfg, params, traffic(), slots=2, max_seq=64,
                     prefill_chunk=4, prefill_adaptive=True)
    st = srv.stats()["prefill"]
    assert ad == base
    assert st["adaptive"] is True
    assert st["max_prompt_steps_per_tick"] >= 24      # uncontended drain
    assert st["max_prompt_steps_contended_tick"] == 0

    # long prompt submitted while a short request is decoding → its prefill
    # is contended and must honor the fixed chunk bound
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=64,
                       prefill_chunk=4, prefill_adaptive=True)
    short = _requests(cfg.vocab, n=1, max_new=6, seed=1)[0]
    srv.submit(short)
    srv.step()                                         # short is now live
    srv.submit(Request(uid=99, prompt=list(long_prompt), max_new_tokens=2))
    srv.run_until_drained()
    st = srv.stats()["prefill"]
    assert 0 < st["max_prompt_steps_contended_tick"] <= 4

    # adaptive without a chunk size is a config error
    with pytest.raises(ValueError, match="prefill_adaptive"):
        DecodeServer(cfg, params, num_slots=2, max_seq=64,
                     prefill_adaptive=True)


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_radix_structure():
    pc = PrefixCache(budget_bytes=1 << 30)
    s1 = {"h": jnp.ones((1, 4))}
    pc.insert([1, 2, 3, 4], s1, logits=jnp.ones(8), resumable=True)
    pc.insert([1, 2, 5], s1, logits=jnp.ones(8), resumable=True)
    pc.insert([1, 2], s1, logits=jnp.ones(8), resumable=True)
    # deepest-first candidates along the path
    got = [e.length for e in pc.lookup([1, 2, 3, 4, 9])]
    assert got == [4, 2]
    got = [e.length for e in pc.lookup([1, 2, 5])]
    assert got == [3, 2]
    assert pc.lookup([2, 1]) == []
    assert pc.telemetry()["entries"] == 3


def test_prefix_cache_lru_eviction():
    pc = PrefixCache(budget_bytes=1)          # everything over budget
    pc.insert([1, 2], {"h": jnp.ones((1, 4))})
    assert pc.telemetry()["evictions"] >= 1
    assert pc.telemetry()["bytes_in_use"] == 0

    big = PrefixCache(budget_bytes=2 * 16 + 8)    # each [1,4] f32 entry = 16B
    for i in range(4):
        big.insert([i, i + 1], {"h": jnp.full((1, 4), float(i))})
    t = big.telemetry()
    assert t["evictions"] == 2 and t["entries"] == 2
    assert t["bytes_in_use"] <= big.budget_bytes
    # the survivors are the most recently inserted prefixes
    assert [e.length for e in big.lookup([2, 3])] == [2]
    assert big.lookup([0, 1]) == []


def test_prefix_cache_eviction_prunes_tree_nodes():
    """Eviction must unlink the dead radix nodes, not just drop their
    entries — otherwise the tree structure (never counted against
    budget_bytes) grows one node per unique evicted prompt, forever."""
    def count_nodes(pc):
        n, stack = 0, [pc.root]
        while stack:
            node = stack.pop()
            n += 1
            stack.extend(node.children.values())
        return n

    pc = PrefixCache(budget_bytes=2 * 16 + 8)  # room for 2 [1,4] f32 entries
    for i in range(200):  # 200 disjoint prefixes through a 2-entry budget
        pc.insert([i, i + 1, i + 2], {"h": jnp.full((1, 4), float(i))})
    t = pc.telemetry()
    assert t["entries"] == 2 and t["bytes_in_use"] <= pc.budget_bytes
    assert count_nodes(pc) <= 1 + 2 * t["entries"]  # root + live paths only
    # shared-prefix splits heal too: evicting a mid node re-merges the edge
    pc2 = PrefixCache(budget_bytes=16)  # one entry fits
    pc2.insert([7, 8, 9, 10], {"h": jnp.ones((1, 4))})
    pc2.insert([7, 8], {"h": jnp.ones((1, 4))})      # splits, evicts the leaf
    pc2.insert([1, 2], {"h": jnp.ones((1, 4))})      # evicts [7,8] as well
    assert [e.length for e in pc2.lookup([1, 2])] == [2]
    assert count_nodes(pc2) == 2                     # root + the [1,2] leaf


def test_prefix_cache_full_hit_recomputes_zero_steps(smollm):
    """Second admission of an identical prompt recomputes 0 prompt steps and
    produces token-identical greedy output (hit vs miss)."""
    cfg, params = smollm
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=48, prefill_chunk=4,
                       prefix_cache_bytes=64 << 20)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    srv.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=5))
    srv.run_until_drained()
    cold_steps = srv.stats()["prefill"]["prompt_steps_computed"]
    assert cold_steps == len(prompt)
    srv.submit(Request(uid=1, prompt=list(prompt), max_new_tokens=5))
    done = srv.run_until_drained()
    st = srv.stats()
    assert st["prefill"]["prompt_steps_computed"] == cold_steps  # 0 more
    assert st["prefix_cache"]["hits"] == 1
    assert st["prefix_cache"]["prompt_steps_saved"] >= len(prompt)
    by = {r.uid: r.out_tokens for r in done}
    assert by[0] == by[1]
    assert done[1].prefix_hit_tokens == len(prompt)


def test_prefix_cache_partial_hit_resumes(smollm):
    """A longer prompt sharing a chunk-aligned prefix resumes mid-prompt and
    still matches the cache-cold greedy output."""
    cfg, params = smollm
    shared = [3, 1, 4, 1, 5, 9, 2, 6]                # 8 = 2 chunks of 4
    longp = shared + [8, 7, 8, 2]
    cold, _ = _drain(cfg, params,
                     [Request(uid=0, prompt=list(longp), max_new_tokens=5)])
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=48, prefill_chunk=4,
                       prefix_cache_bytes=64 << 20)
    srv.submit(Request(uid=0, prompt=list(shared), max_new_tokens=3))
    srv.run_until_drained()
    base_steps = srv.stats()["prefill"]["prompt_steps_computed"]
    srv.submit(Request(uid=1, prompt=list(longp), max_new_tokens=5))
    done = srv.run_until_drained()
    st = srv.stats()
    assert st["prefix_cache"]["partial_hits"] == 1
    # only the 4 unshared tokens were recomputed
    assert st["prefill"]["prompt_steps_computed"] == base_steps + 4
    by = {r.uid: list(r.out_tokens) for r in done}
    assert by[1] == cold[0]
    assert done[1].prefix_hit_tokens == len(shared)


# ---------------------------------------------------------------------------
# scheduler: priorities, aging, admission control
# ---------------------------------------------------------------------------

def test_scheduler_priority_order():
    s = Scheduler(SchedulerConfig(aging_rate=0.0), prompt_limit=100)
    lo = Request(uid=0, prompt=[1], priority=2)
    hi = Request(uid=1, prompt=[1], priority=0)
    mid = Request(uid=2, prompt=[1], priority=1)
    for r in (lo, hi, mid):
        s.admit(r, now=0.0)
    order = [s.next_request(now=0.0).uid for _ in range(3)]
    assert order == [1, 2, 0]


def test_scheduler_fairness_aging():
    """A starved batch request overtakes fresh interactive traffic once its
    wait exceeds the class gap / aging_rate."""
    s = Scheduler(SchedulerConfig(aging_rate=1.0), prompt_limit=100)
    old_batch = Request(uid=0, prompt=[1], priority=5)
    s.admit(old_batch, now=0.0)
    fresh = Request(uid=1, prompt=[1], priority=0)
    s.admit(fresh, now=10.0)   # batch has aged 10s -> effective 5-10 = -5 < 0
    assert s.next_request(now=10.0).uid == 0
    assert s.next_request(now=10.0).uid == 1
    # fifo policy ignores classes entirely
    f = Scheduler(SchedulerConfig(policy="fifo"), prompt_limit=100)
    a = Request(uid=0, prompt=[1], priority=9)
    b = Request(uid=1, prompt=[1], priority=0)
    f.admit(a, now=0.0)
    f.admit(b, now=1.0)
    assert f.next_request(now=1.0).uid == 0


def test_scheduler_admission_control(smollm):
    cfg, params = smollm
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=16,
                       scheduler=SchedulerConfig(max_queue=2))
    # queue bound
    reqs = _requests(cfg.vocab, n=4, max_new=2)
    admitted = [srv.submit(r) for r in reqs]
    assert admitted == [True, True, False, False]
    assert reqs[2].finish_reason == "rejected:queue_full"
    assert reqs[2].done_at is not None
    # empty prompt
    empty = Request(uid=9, prompt=[], max_new_tokens=2)
    assert not srv.submit(empty)
    assert empty.finish_reason == "rejected:empty_prompt"
    done = srv.run_until_drained()
    assert len(done) == 5   # 2 served + 3 rejected


def test_overlength_prompt_rejected_then_truncated(smollm):
    """Prompt length == max_seq must never reach the splice wrap path: the
    default policy rejects, the truncate policy cuts to max_seq-1."""
    cfg, params = smollm
    S = 16
    prompt = list(np.random.default_rng(0).integers(1, cfg.vocab, size=S))
    srv = DecodeServer(cfg, params, num_slots=1, max_seq=S)
    r = Request(uid=0, prompt=list(prompt), max_new_tokens=2)
    assert not srv.submit(r)
    assert r.finish_reason == "rejected:prompt_too_long"
    srv2 = DecodeServer(cfg, params, num_slots=1, max_seq=S,
                        scheduler=SchedulerConfig(overflow="truncate"))
    r2 = Request(uid=1, prompt=list(prompt), max_new_tokens=2)
    assert srv2.submit(r2)
    done = srv2.run_until_drained()
    assert done[0].truncated and len(done[0].prompt) == S - 1
    assert len(done[0].out_tokens) == 2
    # boundary: plen == max_seq - 1 admits fine
    srv3 = DecodeServer(cfg, params, num_slots=1, max_seq=S)
    r3 = Request(uid=2, prompt=list(prompt[: S - 1]), max_new_tokens=1)
    assert srv3.submit(r3)
    assert len(srv3.run_until_drained()[0].out_tokens) == 1


def test_splice_cache_overlength_full_attention_raises(smollm):
    """The p mod W wrap is for sliding-window rings only; an over-length
    full-attention source must raise, not silently corrupt the slot."""
    cfg, params = smollm
    S = 8
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, 12), 1, cfg.vocab)
    _, pc = lm.prefill(params, cfg, toks)
    dst = lm.init_cache(cfg, 2, S)
    with pytest.raises(ValueError, match="reject or truncate"):
        splice_cache(dst, pc, 0, S)
    with pytest.raises(ValueError, match="reject or truncate"):
        splice_cache(dst, pc, 0)              # no max_seq: conservative
    # sliding-window arch: the same over-length splice wraps (ring semantics)
    gcfg = get_smoke_config("gemma3-27b")
    gparams = lm.init_params(gcfg, jax.random.PRNGKey(0))
    gtoks = jax.random.randint(jax.random.PRNGKey(0), (1, 24), 1, gcfg.vocab)
    _, gpc = lm.prefill(gparams, gcfg, gtoks)
    gdst = lm.init_cache(gcfg, 2, 32)         # window=16 < 32: rings may wrap
    out = splice_cache(gdst, gpc, 0, 32)
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(gdst)


# ---------------------------------------------------------------------------
# max_new_tokens off-by-one + admission edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("persistent", [False, True])
def test_max_new_tokens_exact(smollm, persistent):
    """max_new_tokens=N emits exactly N tokens under both drivers — incl.
    N=1 (the off-by-one: prefill's sampled token IS the one token) and N=0."""
    cfg, params = smollm
    reqs = [Request(uid=n, prompt=[1, 2, 3], max_new_tokens=n)
            for n in (0, 1, 2, 5)]
    done, _ = _drain(cfg, params, reqs, persistent=persistent, block_k=4)
    assert {u: len(t) for u, t in done.items()} == {0: 0, 1: 1, 2: 2, 5: 5}


def test_first_token_parity_between_budgets(smollm):
    """The single token of a max_new=1 request equals the first token of a
    larger-budget request with the same prompt."""
    cfg, params = smollm
    one, _ = _drain(cfg, params,
                    [Request(uid=0, prompt=[5, 4, 3], max_new_tokens=1)])
    many, _ = _drain(cfg, params,
                     [Request(uid=0, prompt=[5, 4, 3], max_new_tokens=6)])
    assert one[0] == many[0][:1]


def test_async_server_priorities_and_completion(smollm):
    """asyncio front-end: concurrent generate() calls resolve with the same
    tokens as the synchronous drain; admission rejections resolve instantly."""
    cfg, params = smollm
    sync_out, _ = _drain(cfg, params, _requests(cfg.vocab, n=4, max_new=4),
                         slots=2)

    async def main():
        srv = DecodeServer(cfg, params, num_slots=2, max_seq=48,
                           prefill_chunk=2)
        aserver = AsyncServer(srv)
        reqs = _requests(cfg.vocab, n=4, max_new=4)
        bad = Request(uid=77, prompt=[], max_new_tokens=4)
        results = await asyncio.gather(*(aserver.generate(r) for r in reqs),
                                       aserver.generate(bad))
        return results

    results = asyncio.run(main())
    by = {r.uid: list(r.out_tokens) for r in results}
    assert by[77] == [] and results[-1].finish_reason == "rejected:empty_prompt"
    del by[77]
    assert by == sync_out


# ---------------------------------------------------------------------------
# telemetry fixes
# ---------------------------------------------------------------------------

def test_sampled_decode_counts_extra_syncs(smollm):
    """Legacy step() with temperature>0 pays one extra host↔device
    round-trip per live sampled slot — stats() must count them."""
    cfg, params = smollm
    greedy = [Request(uid=0, prompt=[1, 2, 3], max_new_tokens=5)]
    _, s_g = _drain(cfg, params, greedy, slots=1)
    sampled = [Request(uid=0, prompt=[1, 2, 3], max_new_tokens=5,
                       temperature=0.8)]
    _, s_s = _drain(cfg, params, sampled, slots=1)
    assert s_g.stats()["decoded_tokens"] == s_s.stats()["decoded_tokens"]
    # 4 decode ticks (first token comes from prefill): greedy = 4 syncs,
    # sampled = 4 dispatch syncs + 4 categorical round-trips
    assert s_s.stats()["decode_syncs"] == 2 * s_g.stats()["decode_syncs"]


# ---------------------------------------------------------------------------
# cslow_scan length inference fix
# ---------------------------------------------------------------------------

def test_cslow_scan_none_params_requires_length():
    model = StateSpaceModel(
        f=lambda p, x, u, k: x + u,
        g=lambda p, x, u, k: x,
    )
    x0 = jnp.zeros((2, 3))
    us = jnp.ones((2, 4, 3))
    with pytest.raises(ValueError, match="length"):
        cslow_scan(model, None, x0, us, num_streams=2)
    finals, ys = cslow_scan(model, None, x0, us, num_streams=2, length=4)
    np.testing.assert_allclose(np.asarray(finals), 4 * np.ones((2, 3)))
    assert ys.shape == (2, 4, 3)
