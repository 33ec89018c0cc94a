"""DeepSeek-V2-Lite as published, at smoke size on the CPU: the program's
prefill, decode through the cache, chunked prefill and held-expert layer
against the plain reference (``bench/configs/deepseek-v2-lite-ep8.py``,
which imports nothing of the program), YaRN against the published
formulas, and the donated decode cache of ``DecodeServer``.

Tolerances: program and reference are both float32 on the CPU (the
reference at ``highest``), so they differ only by the order of operations:
2e-4 of the largest logit, as for the benchmark's other configurations.
Where the program is compared with itself (chunked against one-shot, one
request alone against it in a batch) the work per token is the same
arithmetic in another batching, so the bound is 1e-5 of the largest value.
"""

import dataclasses
import functools
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.mla_decode import ops as mla_ops
from repro.models import attention as att
from repro.models import layers, lm
from repro.models import moe as moe_lib

ROOT = Path(__file__).resolve().parents[1]
P, N = 11, 8
decode_step = jax.jit(lm.decode_step, static_argnums=1)


@pytest.fixture(scope="module")
def ref():
    path = ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.py"
    spec = importlib.util.spec_from_file_location("dsv2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sizes_of(cfg):
    """The reference's sizes for a program config."""
    return {
        "num_hidden_layers": cfg.n_layers, "first_k_dense_replace": cfg.first_dense_layers,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "vocab_size": cfg.vocab, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.d_ff_expert, "n_shared_experts": cfg.n_shared_experts,
        "n_routed_experts": cfg.experts_held, "router_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.top_k, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": cfg.rope_yarn_factor, "mscale": cfg.rope_yarn_mscale,
                         "mscale_all_dim": cfg.rope_yarn_mscale_all_dim,
                         "beta_fast": cfg.rope_yarn_beta_fast,
                         "beta_slow": cfg.rope_yarn_beta_slow,
                         "original_max_position_embeddings": cfg.rope_yarn_orig_max_pos},
    }


@pytest.fixture(scope="module")
def model():
    # 4 of 8 experts held: the reference is given the same share
    cfg = dataclasses.replace(get_smoke_config("deepseek-v2-lite-16b"), n_experts_held=4)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, P + N), 0, cfg.vocab)
    return cfg, params, toks


def _ref_logits(ref, cfg, params, toks):
    s = sizes_of(cfg)
    return np.asarray(ref.reference_logits(s, params, ref.reference_hidden(s, params, toks)))


def _close(got, want, rel):
    np.testing.assert_allclose(np.asarray(got), want, atol=rel * np.max(np.abs(want)), rtol=0)


def _prefill_vs_reference(ref, cfg, params, toks):
    logits, _ = lm.prefill(params, cfg, toks[:, :P])
    _close(logits, _ref_logits(ref, cfg, params, toks[:, :P])[:, -1], 2e-4)


def _decode_vs_reference(ref, cfg, params, toks):
    """Prefill P tokens, then decode N through the cache."""
    logits, pc = lm.prefill(params, cfg, toks[:, :P])
    caches = lm.init_cache(cfg, 2, P + N)
    from repro.runtime.server import splice_cache

    for b in range(2):
        caches = splice_cache(caches, jax.tree.map(lambda a, b=b: a[:, b:b + 1], pc), b)
    got = [logits]
    for t in range(P, P + N - 1):
        logits, caches = decode_step(params, cfg, toks[:, t:t + 1], caches,
                                        jnp.full((2,), t, jnp.int32))
        got.append(logits)
    want = _ref_logits(ref, cfg, params, toks)[:, P - 1:P + N - 1]
    _close(np.stack([np.asarray(g) for g in got], axis=1), want, 2e-4)


def _chunked_vs_oneshot(ref, cfg, params, toks):
    del ref
    T = P + N
    want, _ = lm.prefill(params, cfg, toks)
    caches = lm.init_cache(cfg, 2, T)
    for p in range(0, T, 5):
        got, caches = lm.prefill_chunk(params, cfg, toks[:, p:p + 5], caches, jnp.int32(p))
    _close(got, np.asarray(want), 1e-5)


def _alone_vs_in_a_batch(ref, cfg, params, toks):
    """No pick is dropped: a request's logits do not depend on the 7 other
    requests decoded beside it."""
    del ref
    others = jax.random.randint(jax.random.PRNGKey(2), (7, P + N), 0, cfg.vocab)
    runs = []
    for batch in (toks[:1], jnp.concatenate([toks[:1], others])):
        B = batch.shape[0]
        logits, caches = lm.prefill(params, cfg, batch[:, :P])
        caches = jax.tree.map(
            lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, N)] + [(0, 0)] * (a.ndim - 3)), caches)
        out = [logits[0]]
        for t in range(P, P + N - 1):
            logits, caches = decode_step(params, cfg, batch[:, t:t + 1], caches,
                                            jnp.full((B,), t, jnp.int32))
            out.append(logits[0])
        runs.append(np.stack([np.asarray(o) for o in out]))
    _close(runs[1], runs[0], 1e-5)


def _shares_add_up(ref, cfg, params, toks):
    """Eight chips each hold one of the 8 experts: their held-expert outputs,
    with the shared experts counted once, add up to the uncut layer."""
    del toks
    full = dataclasses.replace(cfg, n_experts_held=0)
    p = moe_lib.moe_params(jax.random.PRNGKey(3), full)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
    uncut = np.asarray(moe_lib.held_experts_apply(p, full, x))
    shared = moe_lib.mlp_apply(p["shared"], x, act=cfg.mlp_act)
    E = cfg.n_experts
    one = dataclasses.replace(cfg, n_experts_held=1)
    # the chip holding expert e sees it as its expert 0: the router's
    # columns turned so that column e comes first, the same top-k picks
    parts = [moe_lib.held_experts_apply(
        dict(p, router=jnp.roll(p["router"], -e, axis=1),
             **{n: p[n][e:e + 1] for n in ("w_in", "w_gate", "w_out")}), one, x)
        for e in range(E)]
    _close(sum(parts) - (E - 1) * shared, uncut, 1e-5)
    # and the uncut layer is the reference's, which computes every expert
    # for every token and weights it by its (unnormalised) top-k probability
    _close(uncut, np.asarray(ref._moe(x, p, K=cfg.top_k, mm=ref.f32_dot)), 2e-4)


CASES = {"prefill": _prefill_vs_reference, "decode": _decode_vs_reference,
         "chunked": _chunked_vs_oneshot, "batch": _alone_vs_in_a_batch,
         "shares": _shares_add_up}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case, ref, model):
    CASES[case](ref, *model)


def test_yarn_at_published_settings(ref):
    """YaRN against DeepSeek-V2's published formulas: ``yarn_get_mscale``,
    and frequencies blended by the linear ramp between the correction dims
    (pairs 0-9 keep theta^(-2i/64), pairs 23-31 are slowed 40-fold)."""
    cfg = get_config("deepseek-v2-lite-16b")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert layers.yarn_get_mscale(40, 0.707) == pytest.approx(m)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    assert layers.yarn_get_mscale(1.0, 0.707) == 1.0
    assert att.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    freqs = np.asarray(layers.yarn_freqs(64, 1e4, 40, 4096, 32, 1), np.float64)
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    corr = lambda rot: 64 * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(1e4))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(freqs, extra / 40 * ramp + extra * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(freqs[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], extra[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(freqs, ref.yarn_inv_freq(sizes_of(cfg)), rtol=1e-6)
    assert ref.softmax_scale(sizes_of(cfg)) == pytest.approx(att.mla_softmax_scale(cfg))


def test_published_block():
    """The full config is DeepSeek-V2-Lite's block: a leading dense layer,
    26 MoE layers routing over 64 experts, top-6 unnormalised, 2 shared."""
    cfg = get_config("deepseek-v2-lite-16b")
    assert (cfg.first_dense_layers, cfg.d_ff, cfg.n_groups, cfg.layer_pattern) == \
        (1, 10944, 26, ("moe",))
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.n_shared_experts) == (64, 64, 6, 2)
    assert not cfg.norm_topk_prob
    shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == pytest.approx(15.7e9, rel=5e-3)


def _greedy_alone(cfg, params, prompt, max_new, S):
    """One request's greedy tokens through undonated, eager programs."""
    from repro.runtime.server import splice_cache

    logits, pc = lm.prefill(params, cfg, jnp.asarray([prompt], jnp.int32))
    caches = splice_cache(lm.init_cache(cfg, 1, S), pc, 0, S)
    out = [int(jnp.argmax(logits[0]))]
    for pos in range(len(prompt), len(prompt) + max_new - 1):
        logits, caches = decode_step(params, cfg, jnp.asarray([[out[-1]]], jnp.int32),
                                        caches, jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.mark.parametrize("arch", ["paper-lstm", "falcon-mamba-7b", "deepseek-v2-lite-16b"])
def test_server_cache_lives_once(arch):
    """A decode block and a splice take the cache donated and leave one live
    copy (every old handle is deleted), and the served greedy tokens are the
    undonated programs' tokens for each request alone."""
    from repro.runtime import DecodeServer, Request

    cfg = get_smoke_config(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    S = 32
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 9, 7)]
    srv = DecodeServer(cfg, params, num_slots=4, max_seq=S, persistent=True, block_k=4)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=7) for i, p in enumerate(prompts[:2])]
    for r in reqs:
        srv.submit(r)
    old = jax.tree.leaves(srv.caches)
    srv.step_block()                       # two splices, then one block
    assert all(a.is_deleted() for a in old)
    reqs.append(Request(uid=2, prompt=prompts[2], max_new_tokens=7))
    srv.submit(reqs[-1])
    old = jax.tree.leaves(srv.caches)
    srv._admit()                           # one splice
    assert all(a.is_deleted() for a in old)
    srv.run_until_drained()
    for r, p in zip(reqs, prompts):
        assert r.out_tokens == _greedy_alone(cfg, params, p, 7, S), r.uid


def _latent_path(mp, path):
    """Route the decode tick's latent products: the ``mla_decode`` kernel
    over 8-row tiles (so a smoke cache spans several), or the jnp products
    over the whole cache."""
    if path == "kernel":
        fn = functools.partial(mla_ops.mla_decode, block_rows=8)
    else:
        def fn(q_lat, q_rope, c_kv, k_rope, layer, pos, *, scale):
            return mla_ops.mla_decode_ref(q_lat[:, None], q_rope[:, None], c_kv, k_rope,
                                          layer, pos[:, None], scale=scale)[:, 0]
    mp.setattr(att.mla_ops, "mla_decode", fn)


def test_mla_decode_kernel_matches_jnp_path(model):
    """Decode ticks through the kernel give the jnp path's logits and
    caches: the same arithmetic, another order of summation (a later
    layer's new row follows the earlier layers' attention)."""
    cfg, params, toks = model
    logits0, pc = lm.prefill(params, cfg, toks[:, :P])
    from repro.runtime.server import splice_cache

    runs = []
    for path in ("kernel", "jnp"):
        caches = lm.init_cache(cfg, 2, P + N)
        for b in range(2):
            caches = splice_cache(caches, jax.tree.map(lambda a, b=b: a[:, b:b + 1], pc), b)
        with pytest.MonkeyPatch.context() as mp:
            _latent_path(mp, path)
            step = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))
            out = []
            for t in range(P, P + N - 1):      # positions 11-17: 2 and 3 tiles
                logits, caches = step(params, toks[:, t:t + 1], caches,
                                      jnp.asarray([t, t - 4], jnp.int32))
                out.append(np.asarray(logits))
        runs.append((np.stack(out), jax.tree.map(np.asarray, caches)))
    _close(runs[0][0], runs[1][0], 1e-5)
    jax.tree.map(lambda a, b: _close(a, b, 1e-5), runs[0][1], runs[1][1])


def test_server_greedy_tokens_kernel_vs_jnp_path():
    """32 decode ticks of DecodeServer give the same greedy tokens through
    the kernel as through the jnp path."""
    from repro.runtime import DecodeServer, Request

    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (3, 12, 7)]
    served = []
    for path in ("kernel", "jnp"):
        with pytest.MonkeyPatch.context() as mp:
            _latent_path(mp, path)
            srv = DecodeServer(cfg, params, num_slots=4, max_seq=48, persistent=True,
                               block_k=4)
            for i, p in enumerate(prompts):
                srv.submit(Request(uid=i, prompt=p, max_new_tokens=33))
            done = srv.run_until_drained()
        assert srv.decode_syncs * 4 == 32
        served.append({r.uid: r.out_tokens for r in done})
    assert served[0] == served[1]


def test_chunked_prefill_takes_the_jnp_path(model, monkeypatch):
    """A chunk of several tokens (resumable prefill) runs the jnp products;
    only a one-token decode tick runs the kernel."""
    cfg, params, toks = model

    def kernel(*a, **k):
        raise AssertionError("the mla_decode kernel ran")

    monkeypatch.setattr(att.mla_ops, "mla_decode", kernel)
    caches = lm.init_cache(cfg, 2, P + N)
    _, caches = jax.jit(lambda p, t, c: lm.prefill_chunk(p, cfg, t, c, jnp.int32(0)))(
        params, toks[:, :5], caches)
    with pytest.raises(AssertionError, match="kernel ran"):
        jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c, jnp.int32(5)))(
            params, toks[:, 5:6], caches)


def test_latent_rows_hand_count():
    """``latent_rows``: over one block of 4 ticks, slot 0 (prompt 510) is
    queried at 510, 511, 512 and, retired, again at 513; slot 1 (prompt 20)
    at 20-23.  With 512-row tiles and 3 MLA layers that is (1 + 1 + 2 + 2)
    + 4 tiles read, of 4 ticks x 2 slots x 1100 rows reserved."""
    from repro.runtime import DecodeServer, Request
    from repro.runtime.server import mla_layers

    cfg = get_smoke_config("deepseek-v2-lite-16b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    S = 1100
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=S, persistent=True, block_k=4)
    assert mla_layers(srv.caches) == 3 and mla_ops.tile_rows(S) == 512
    srv.submit(Request(uid=0, prompt=[1] * 510, max_new_tokens=4))
    srv.submit(Request(uid=1, prompt=[2] * 20, max_new_tokens=6))
    srv.step_block()
    rows = srv.stats()["latent_rows"]
    assert rows["read"] == (6 + 4) * 512 * 3
    assert rows["reserved"] == 4 * 2 * S * 3
    assert rows["share"] == pytest.approx(rows["read"] / rows["reserved"])
    # the per-token driver counts its one tick: slot 0 at 513, slot 1 at 24
    srv.step()
    assert srv.stats()["latent_rows"]["read"] == (6 + 4 + 2 + 1) * 512 * 3


def test_latent_rows_only_for_mla_models():
    from repro.runtime import DecodeServer

    cfg = get_smoke_config("falcon-mamba-7b")
    srv = DecodeServer(cfg, lm.init_params(cfg, jax.random.PRNGKey(0)), num_slots=2,
                       max_seq=16)
    assert "latent_rows" not in srv.stats()
