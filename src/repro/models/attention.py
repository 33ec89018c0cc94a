"""Attention variants: GQA, sliding-window, MLA (DeepSeek), cross-attention.

Every variant offers a *prefill* path (full sequence) and a *decode* path
(one query token against a cache) — the serving state-space view: the KV
cache (or MLA's low-rank latent) is the **state vector**, decode is the
state-update `f`, and the logits head is the output map `g`.

Pure jnp by default (dry-run/CPU safe); ``use_pallas=True`` routes the
prefill attention core to the Pallas flash kernel (validated in interpret
mode in tests).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_params, yarn_freqs, yarn_get_mscale

PyTree = Any

NEG_INF = -2.0 ** 30  # large-but-finite: keeps softmax NaN-free on fully-masked rows


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def gqa_params(key, cfg: ModelConfig, lora_rank: int = 0) -> PyTree:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), cfg.p_dtype),
        "wk": dense_init(ks[1], (D, KV * hd), cfg.p_dtype),
        "wv": dense_init(ks[2], (D, KV * hd), cfg.p_dtype),
        "wo": dense_init(ks[3], (H * hd, D), cfg.p_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_params(hd, cfg.p_dtype)
        p["k_norm"] = rmsnorm_params(hd, cfg.p_dtype)
    if lora_rank:  # zamba2-style per-application LoRA deltas on q/k/v
        p["lora"] = {
            "qA": dense_init(ks[4], (D, lora_rank), cfg.p_dtype),
            "qB": jnp.zeros((lora_rank, H * hd), cfg.p_dtype),
            "kA": dense_init(ks[5], (D, lora_rank), cfg.p_dtype),
            "kB": jnp.zeros((lora_rank, KV * hd), cfg.p_dtype),
            "vA": dense_init(ks[6], (D, lora_rank), cfg.p_dtype),
            "vB": jnp.zeros((lora_rank, KV * hd), cfg.p_dtype),
        }
    return p


def mla_params(key, cfg: ModelConfig) -> PyTree:
    D, H = cfg.d_model, cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (D, H * (dn + dr)), cfg.p_dtype),
        "w_dkv": dense_init(ks[1], (D, r), cfg.p_dtype),        # down: shared latent
        "w_krope": dense_init(ks[2], (D, dr), cfg.p_dtype),     # shared rope key
        "w_uk": dense_init(ks[3], (r, H * dn), cfg.p_dtype),    # up: per-head keys
        "w_uv": dense_init(ks[4], (r, H * dv), cfg.p_dtype),    # up: per-head values
        "wo": dense_init(ks[5], (H * dv, D), cfg.p_dtype),
        "kv_norm": rmsnorm_params(r, cfg.p_dtype),
    }


def cross_attn_params(key, cfg: ModelConfig) -> PyTree:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (D, H * hd), cfg.p_dtype),
        "wk": dense_init(ks[1], (cfg.frontend_dim, KV * hd), cfg.p_dtype),
        "wv": dense_init(ks[2], (cfg.frontend_dim, KV * hd), cfg.p_dtype),
        "wo": dense_init(ks[3], (H * hd, D), cfg.p_dtype),
        "gate": jnp.zeros((1,), cfg.p_dtype),  # tanh-gated residual (llama-vision)
        "q_norm": rmsnorm_params(hd, cfg.p_dtype),
        "k_norm": rmsnorm_params(hd, cfg.p_dtype),
    }


# ---------------------------------------------------------------------------
# attention core (shared): grouped-query scaled dot-product w/ masking
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask, softcap: float = 0.0):
    """q: [B,S,H,hd], k/v: [B,T,KV,hd(v)], mask: broadcastable [B,1,S,T] bool.
    GQA via head grouping — no KV repetition is materialized."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores *= hd ** -0.5
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def causal_mask(S: int, T: int, offset: int = 0, window: int = 0, causal: bool = True):
    """[1, 1, S, T] boolean mask.  ``offset`` = absolute position of query 0.
    ``window``>0 restricts to a trailing sliding window."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    m = jnp.ones((S, T), bool) if not causal else kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None]


# ---------------------------------------------------------------------------
# GQA forward: prefill + decode
# ---------------------------------------------------------------------------

def _project_qkv(p, cfg: ModelConfig, x):
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "lora" in p:
        lo = p["lora"]
        q += (x @ lo["qA"]) @ lo["qB"]
        k += (x @ lo["kA"]) @ lo["kB"]
        v += (x @ lo["vA"]) @ lo["vB"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def gqa_prefill(p, cfg: ModelConfig, x, *, window: int = 0, positions=None):
    """Full-sequence attention.  Returns (out, (k, v)) for cache seeding."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary)
    if cfg.use_pallas:
        from repro.kernels.flash_attention import ops as fops

        out = fops.flash_attention(q, k, v, causal=cfg.causal, window=window,
                                   softcap=cfg.attn_logit_softcap)
    else:
        mask = causal_mask(S, S, window=window, causal=cfg.causal)
        out = _sdpa(q, k, v, mask, cfg.attn_logit_softcap)
    return out.reshape(B, S, -1) @ p["wo"], (k, v)


def _posv(pos, B):
    """Normalize decode position to a per-sequence [B] vector."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))


def gqa_decode(p, cfg: ModelConfig, x, cache: PyTree, pos, *, window: int = 0):
    """Cache-resident step for S ≥ 1 query tokens starting at ``pos``.

    cache = {"k": [B, S_max, KV, hd], "v": ...}; ``pos``: scalar or [B]
    int32 (per-sequence positions for continuous batching).  S == 1 is the
    classic decode tick; S > 1 is a *chunked-prefill* continuation — the same
    state update applied to a block of inputs, causal within the chunk.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    posv = _posv(pos, B)
    qpos = posv[:, None] + jnp.arange(S)[None, :]            # [B, S] absolute
    q = apply_rope(q, qpos, cfg.rope_theta, cfg.partial_rotary)
    k = apply_rope(k, qpos, cfg.rope_theta, cfg.partial_rotary)
    bidx = jnp.arange(B)
    ck = cache["k"].at[bidx[:, None], qpos].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx[:, None], qpos].set(v.astype(cache["v"].dtype))
    T = ck.shape[1]
    kpos = jnp.arange(T)[None, None, None, :]
    mask = kpos <= qpos[:, None, :, None]
    if window > 0:
        mask &= kpos > (qpos - window)[:, None, :, None]
    out = _sdpa(q, ck, cv, mask, cfg.attn_logit_softcap)
    return out.reshape(B, S, -1) @ p["wo"], {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): compressed-latent cache; naive prefill + absorbed decode
# ---------------------------------------------------------------------------

def _mla_rope(cfg: ModelConfig, x, positions):
    """MLA's rotary embedding: plain RoPE, or YaRN's frequencies with its
    cos/sin scale where ``rope_yarn_factor`` is set."""
    if not cfg.rope_yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    f = cfg.rope_yarn_factor
    freqs = yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, f, cfg.rope_yarn_orig_max_pos,
                       cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow)
    mscale = yarn_get_mscale(f, cfg.rope_yarn_mscale) / yarn_get_mscale(
        f, cfg.rope_yarn_mscale_all_dim)
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, mscale=mscale)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(dn + dr)^-1/2, times YaRN's mscale squared where it is set."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn_factor and cfg.rope_yarn_mscale_all_dim:
        scale *= yarn_get_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim) ** 2
    return scale


def _mla_q(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _mla_rope(cfg, q_rope, positions)
    return q_nope, q_rope


def mla_prefill(p, cfg: ModelConfig, x, positions=None):
    """Naive (expanded) prefill: up-project latent to per-head K/V."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)

    c_kv = rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)        # [B,S,r]
    k_rope = _mla_rope(cfg, (x @ p["w_krope"]).reshape(B, S, 1, dr), positions)
    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, dn)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, dv)

    scale = mla_softmax_scale(cfg)
    s_nope = jnp.einsum("bshd,bthd->bhst", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
    s_rope = jnp.einsum("bshd,bthd->bhst", q_rope.astype(jnp.float32),
                        jnp.broadcast_to(k_rope, (B, S, 1, dr)).astype(jnp.float32))
    scores = (s_nope + s_rope) * scale
    mask = causal_mask(S, S)[:, 0]  # [1,S,T] -> broadcast over H
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, S, -1) @ p["wo"], (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, cfg: ModelConfig, x, cache: PyTree, pos, layer=None):
    """Absorbed decode (the MLA serving trick): attend in the latent space.

    cache = {"c_kv": [B, S_max, r], "k_rope": [B, S_max, dr]} — 576 floats
    per token per layer instead of 2·H·hd = 4096: the low-rank *state*.
    With ``layer`` the leaves are the whole stack [G, B, S_max, ...] and
    this layer's rows are written into it in place, then read back: the
    stack is never copied.
    W_UK is absorbed into the query, W_UV into the output:
        score = (q_nope Wuk_h) · c_kv + q_rope · k_rope
        out_h = (probs · c_kv) Wuv_h
    """
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    posv = _posv(pos, B)
    qpos = posv[:, None] + jnp.arange(S)[None, :]            # [B, S] absolute
    q_nope, q_rope = _mla_q(p, cfg, x, qpos)

    c_new = rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)
    kr_new = _mla_rope(cfg, (x @ p["w_krope"]).reshape(B, S, 1, dr), qpos)[:, :, 0]
    bidx = jnp.arange(B)
    at = (bidx[:, None], qpos) if layer is None else (layer, bidx[:, None], qpos)
    new_cache = {"c_kv": cache["c_kv"].at[at].set(c_new.astype(cache["c_kv"].dtype)),
                 "k_rope": cache["k_rope"].at[at].set(kr_new.astype(cache["k_rope"].dtype))}
    c_kv, k_rope = new_cache["c_kv"], new_cache["k_rope"]
    if layer is not None:
        c_kv, k_rope = c_kv[layer], k_rope[layer]

    w_uk = p["w_uk"].reshape(r, H, dn)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
    s_lat = jnp.einsum("bshr,btr->bhst", q_lat, c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    scores = (s_lat + s_rope) * mla_softmax_scale(cfg)
    T = c_kv.shape[1]
    mask = jnp.arange(T)[None, None, None, :] <= qpos[:, None, :, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, c_kv.astype(jnp.float32))
    w_uv = p["w_uv"].reshape(r, H, dv)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, S, -1) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# Cross-attention (vision/audio memory; llama-3.2-vision style)
# ---------------------------------------------------------------------------

def cross_attn(p, cfg: ModelConfig, x, memory):
    """x: [B,S,D] attends to memory [B,M,frontend_dim]; tanh-gated residual."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    M = memory.shape[1]
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (memory @ p["wk"]).reshape(B, M, KV, hd)
    v = (memory @ p["wv"]).reshape(B, M, KV, hd)
    q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
    k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    mask = jnp.ones((1, 1, S, M), bool)
    out = _sdpa(q, k, v, mask)
    return jnp.tanh(p["gate"]) * (out.reshape(B, S, -1) @ p["wo"])
