"""Block assembly: heterogeneous layer stacks as a uniform scan over groups.

The paper's layer-wise resource sharing (§IV-A) is realized by scanning one
compiled *group body* over stacked parameters.  A group is one period of the
architecture's block pattern (``ModelConfig.layer_pattern``):

    dense            -> ("attn",)
    gemma3           -> ("attn_local",)*5 + ("attn",)         # 5:1 local:global
    llama-vision     -> ("attn",)*4 + ("cross",)
    moe              -> ("moe",)        (deepseek: after its dense first_dense_layers)
    falcon-mamba     -> ("mamba1",)
    zamba2           -> ("mamba2",)*k + ("shared_attn",)       # shared weights!

Zamba2's shared transformer block is the paper's resource sharing taken
literally: ONE set of attention/MLP weights is closed over by the scan body
(hoisted — gathered once, reused every group) while per-application LoRA
deltas ride in the stacked group params.

Decode caches are pytrees stacked over groups and threaded through the scan
as (xs → ys): the state vector of the serving-time state-space system.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.recurrent import block as rnn_lib

from . import attention as attn_lib
from . import moe as moe_lib
from . import ssm as ssm_lib
from .config import ModelConfig
from .layers import mlp_apply, mlp_params, rmsnorm, rmsnorm_params

PyTree = Any


# ---------------------------------------------------------------------------
# per-block parameter construction
# ---------------------------------------------------------------------------

def _block_params(key, cfg: ModelConfig, kind: str) -> PyTree:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in ("attn", "attn_local"):
        ap = attn_lib.mla_params(k1, cfg) if cfg.use_mla else attn_lib.gqa_params(k1, cfg)
        return {
            "ln_attn": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "attn": ap,
            "ln_mlp": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "mlp": mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.p_dtype),
        }
    if kind == "moe":
        ap = attn_lib.mla_params(k1, cfg) if cfg.use_mla else attn_lib.gqa_params(k1, cfg)
        return {
            "ln_attn": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "attn": ap,
            "ln_mlp": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "moe": moe_lib.moe_params(k2, cfg),
        }
    if kind == "cross":
        return {
            "ln_attn": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "cross": attn_lib.cross_attn_params(k1, cfg),
            "ln_mlp": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "mlp": mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.p_dtype),
        }
    if kind == "mamba1":
        return {"ln": rmsnorm_params(cfg.d_model, cfg.p_dtype),
                "mamba": ssm_lib.mamba1_params(k1, cfg)}
    if kind == "mamba2":
        return {"ln": rmsnorm_params(cfg.d_model, cfg.p_dtype),
                "mamba": ssm_lib.mamba2_params(k1, cfg)}
    if kind == "recurrent":
        return {"ln": rmsnorm_params(cfg.d_model, cfg.p_dtype),
                "rnn": rnn_lib.recurrent_params(k1, cfg)}
    if kind == "shared_attn":
        # Only the per-application pieces live here; weights are shared.
        return {
            "ln_attn": rmsnorm_params(cfg.d_model, cfg.p_dtype),
            "lora": attn_lib.gqa_params(k1, cfg, lora_rank=cfg.shared_attn_lora_rank)["lora"],
            "ln_mlp": rmsnorm_params(cfg.d_model, cfg.p_dtype),
        }
    raise ValueError(kind)


def group_params(key, cfg: ModelConfig) -> PyTree:
    pat = cfg.layer_pattern
    keys = jax.random.split(key, len(pat))
    return {f"b{i}_{kind}": _block_params(keys[i], cfg, kind) for i, kind in enumerate(pat)}


def lead_params(key, cfg: ModelConfig) -> PyTree | None:
    """The ``first_dense_layers`` dense blocks before the scanned groups,
    stacked like a group of one ``attn`` block (deepseek's
    ``first_k_dense_replace``)."""
    if not cfg.first_dense_layers:
        return None
    keys = jax.random.split(key, cfg.first_dense_layers)
    return jax.vmap(lambda k: {"b0_attn": _block_params(k, cfg, "attn")})(keys)


def shared_block_params(key, cfg: ModelConfig) -> PyTree | None:
    """Zamba2's single shared transformer block (attention + MLP)."""
    if "shared_attn" not in cfg.layer_pattern:
        return None
    k1, k2 = jax.random.split(key)
    base = attn_lib.gqa_params(k1, cfg)
    return {
        "attn": base,
        "mlp": mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.p_dtype),
    }


# ---------------------------------------------------------------------------
# cache construction (decode state)
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int) -> PyTree | None:
    dt = cfg.act_dtype
    if kind in ("attn", "moe", "shared_attn"):
        if cfg.use_mla and kind != "shared_attn":
            return {
                "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dt),
                "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dt),
            }
        return {
            "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dt),
        }
    if kind == "attn_local":
        s = min(max_seq, cfg.sliding_window)
        return {
            "k": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dt),
        }
    if kind == "mamba1":
        return ssm_lib.mamba1_init_state(cfg, batch)
    if kind == "mamba2":
        return ssm_lib.mamba2_init_state(cfg, batch)
    if kind == "recurrent":
        return rnn_lib.recurrent_init_state(cfg, batch)
    if kind == "cross":
        return jnp.zeros((1,), jnp.float32)  # vision memory is static; dummy state
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> PyTree:
    """Decode cache: {"groups": stacked-over-G, "tail": per-block} — the
    serving state vector."""
    pat = cfg.layer_pattern

    def stack(n, blocks):
        # every state starts at zero: each stack is made whole, never as a
        # copy of one layer's buffer (the stacks are the largest arrays a
        # server holds, and a copy would hold them twice while it runs)
        one = jax.eval_shape(lambda: {f"b{i}_{kind}": _block_cache(cfg, kind, batch, max_seq)
                                      for i, kind in enumerate(blocks)})
        return jax.tree.map(lambda s: jnp.zeros((n,) + s.shape, s.dtype), one)

    cache = {"groups": stack(cfg.n_groups, pat)}
    if cfg.first_dense_layers:
        cache["lead"] = stack(cfg.first_dense_layers, ("attn",))
    if cfg.tail_pattern:
        cache["tail"] = {
            f"t{i}_{kind}": _block_cache(cfg, kind, batch, max_seq)
            for i, kind in enumerate(cfg.tail_pattern)
        }
    return cache


def tail_params(key, cfg: ModelConfig) -> PyTree | None:
    if not cfg.tail_pattern:
        return None
    keys = jax.random.split(key, len(cfg.tail_pattern))
    return {
        f"t{i}_{kind}": _block_params(keys[i], cfg, kind)
        for i, kind in enumerate(cfg.tail_pattern)
    }


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _local_window_cache_update(cache, k, v, pos):
    """Ring-buffer write for sliding-window caches: slot = pos mod window."""
    W = cache["k"].shape[1]
    B = k.shape[0]
    slot = jnp.mod(pos, W)
    bidx = jnp.arange(B)
    ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    return {"k": ck, "v": cv}


def apply_block(
    p_blk: PyTree,
    shared: PyTree | None,
    cfg: ModelConfig,
    kind: str,
    x: jnp.ndarray,
    *,
    memory=None,
    cache=None,
    pos=None,
    mode: str = "train",
    layer=None,
):
    """One block, all kinds, all modes.  Returns (x, new_cache, aux_loss).

    ``layer`` (serving modes): this block is the ``layer``-th of its stack;
    an MLA decode or chunk ``cache`` is then the whole stack (see
    ``attention.mla_decode``), and so are the held experts' weights.

    mode="chunk" is the *resumable prefill* step: S ≥ 1 tokens applied
    against an existing cache at offset ``pos`` — the same state-update map
    as decode, batched over a chunk of inputs (attention paths write the
    chunk into the cache and mask causally; SSM/recurrent paths resume
    their scan from the carried state).  Chaining chunks reproduces the
    one-shot prefill trajectory.
    """
    aux = jnp.zeros((), jnp.float32)
    decode = mode in ("decode", "chunk")
    chunk = mode == "chunk"

    if kind in ("attn", "attn_local", "moe"):
        window = cfg.sliding_window if kind == "attn_local" else 0
        acfg = cfg
        if kind == "attn" and cfg.global_every and getattr(cfg, "rope_theta_global", 0):
            acfg = dataclasses.replace(cfg, rope_theta=cfg.rope_theta_global)
        h = rmsnorm(p_blk["ln_attn"], x, cfg.norm_eps)
        if cfg.use_mla:
            with jax.named_scope("mla"):
                if decode:
                    a, cache = attn_lib.mla_decode(p_blk["attn"], acfg, h, cache, pos, layer)
                else:
                    a, kv = attn_lib.mla_prefill(p_blk["attn"], acfg, h)
                    cache = {"c_kv": kv[0], "k_rope": kv[1]} if mode == "prefill" else None
        else:
            if decode:
                if kind == "attn_local":
                    if chunk:
                        a, cache = _gqa_local_chunk(p_blk["attn"], acfg, h, cache, pos)
                    else:
                        a, cache = _gqa_decode_local(p_blk["attn"], acfg, h, cache, pos)
                else:
                    a, cache = attn_lib.gqa_decode(p_blk["attn"], acfg, h, cache, pos)
            else:
                a, kv = attn_lib.gqa_prefill(p_blk["attn"], acfg, h, window=window)
                cache = {"k": kv[0], "v": kv[1]} if mode == "prefill" else None
        x = x + a
        h = rmsnorm(p_blk["ln_mlp"], x, cfg.norm_eps)
        if kind == "moe" and mode == "train":
            y, aux = moe_lib.moe_apply(
                p_blk["moe"], cfg, h, group_size=cfg.moe_group_size or 2048
            )
        elif kind == "moe":
            y = moe_lib.held_experts_apply(p_blk["moe"], cfg, h, layer=layer)
        else:
            y = mlp_apply(p_blk["mlp"], h, cfg.mlp_act)
        return x + y, cache, aux

    if kind == "cross":
        h = rmsnorm(p_blk["ln_attn"], x, cfg.norm_eps)
        x = x + attn_lib.cross_attn(p_blk["cross"], cfg, h, memory)
        h = rmsnorm(p_blk["ln_mlp"], x, cfg.norm_eps)
        return x + mlp_apply(p_blk["mlp"], h, cfg.mlp_act), cache, aux

    if kind in ("mamba1", "mamba2"):
        fn_pre = ssm_lib.mamba1_prefill if kind == "mamba1" else ssm_lib.mamba2_prefill
        fn_dec = ssm_lib.mamba1_decode if kind == "mamba1" else ssm_lib.mamba2_decode
        h = rmsnorm(p_blk["ln"], x, cfg.norm_eps)
        if chunk:
            y, cache = fn_pre(p_blk["mamba"], cfg, h, state=cache)
        elif decode:
            y, cache = fn_dec(p_blk["mamba"], cfg, h, cache)
        else:
            y, st = fn_pre(p_blk["mamba"], cfg, h)
            cache = st if mode == "prefill" else None
        return x + y, cache, aux

    if kind == "recurrent":
        # LSTM/GRU cell: the serving state IS the (h, c) carry (paper eq. 1)
        h = rmsnorm(p_blk["ln"], x, cfg.norm_eps)
        if chunk:
            y, cache = rnn_lib.recurrent_prefill(p_blk["rnn"], cfg, h, state=cache)
        elif decode:
            y, cache = rnn_lib.recurrent_decode(p_blk["rnn"], cfg, h, cache)
        else:
            y, st = rnn_lib.recurrent_prefill(p_blk["rnn"], cfg, h)
            cache = st if mode == "prefill" else None
        return x + y, cache, aux

    if kind == "shared_attn":
        # shared weights + this application's LoRA deltas
        ap = dict(shared["attn"])
        ap["lora"] = p_blk["lora"]
        h = rmsnorm(p_blk["ln_attn"], x, cfg.norm_eps)
        if decode:
            a, cache = attn_lib.gqa_decode(ap, cfg, h, cache, pos)
        else:
            a, kv = attn_lib.gqa_prefill(ap, cfg, h)
            cache = {"k": kv[0], "v": kv[1]} if mode == "prefill" else None
        x = x + a
        h = rmsnorm(p_blk["ln_mlp"], x, cfg.norm_eps)
        return x + mlp_apply(shared["mlp"], h, cfg.mlp_act), cache, aux

    raise ValueError(kind)


def _gqa_decode_local(p, cfg: ModelConfig, x, cache, pos):
    """Decode against a ring-buffer sliding-window cache.

    Keys in the ring carry their absolute position ``kpos`` implicitly:
    slot s holds position p where p ≡ s (mod W) and pos-W < p <= pos.
    RoPE phases are computed from the absolute positions, so we rebuild
    kpos = pos - ((pos - s) mod W) per slot.
    """
    B, S, _ = x.shape
    q, k, v = attn_lib._project_qkv(p, cfg, x)
    posv = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = attn_lib.apply_rope(q, posv[:, None], cfg.rope_theta, cfg.partial_rotary)
    k = attn_lib.apply_rope(k, posv[:, None], cfg.rope_theta, cfg.partial_rotary)
    cache = _local_window_cache_update(cache, k, v, posv)
    W = cache["k"].shape[1]
    slots = jnp.arange(W)[None, :]
    kpos = posv[:, None] - jnp.mod(posv[:, None] - slots, W)  # [B,W] absolute
    mask = (kpos >= 0) & (kpos >= posv[:, None] - W + 1) & (kpos <= posv[:, None])
    out = attn_lib._sdpa(q, cache["k"], cache["v"], mask[:, None, None, :], cfg.attn_logit_softcap)
    return out.reshape(B, S, -1) @ p["wo"], cache


def _gqa_local_chunk(p, cfg: ModelConfig, x, cache, pos):
    """Chunked-prefill step against a ring-buffer sliding-window cache.

    A multi-token chunk cannot scatter-then-attend like the S=1 decode path:
    writing the chunk's keys into the ring may overwrite positions that
    earlier *queries of the same chunk* still need.  So attention runs over
    the concatenation [old ring ∥ chunk keys] with absolute-position masks,
    and only afterwards are the chunk's last min(S, W) tokens committed to
    the ring (earlier chunk tokens are out-of-window for every future query).
    """
    B, S, _ = x.shape
    q, k, v = attn_lib._project_qkv(p, cfg, x)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    qpos = posv[:, None] + jnp.arange(S)[None, :]                  # [B, S]
    q = attn_lib.apply_rope(q, qpos, cfg.rope_theta, cfg.partial_rotary)
    k = attn_lib.apply_rope(k, qpos, cfg.rope_theta, cfg.partial_rotary)

    W = cache["k"].shape[1]
    slots = jnp.arange(W)[None, :]
    # ring slot s holds the latest already-written position p ≡ s (mod W),
    # i.e. p ≤ pos-1; negative ⇒ never written (masked below)
    ring_pos = (posv[:, None] - 1) - jnp.mod(posv[:, None] - 1 - slots, W)
    kpos = jnp.concatenate(
        [jnp.broadcast_to(ring_pos, (B, W)), qpos], axis=1)        # [B, W+S]
    k_all = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=1)
    mask = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None]) \
        & (kpos[:, None, :] > qpos[:, :, None] - W)
    out = attn_lib._sdpa(q, k_all, v_all, mask[:, None], cfg.attn_logit_softcap)

    # commit the trailing min(S, W) chunk tokens to the ring
    Wp = min(S, W)
    tail_pos = qpos[:, S - Wp:]                                    # [B, Wp]
    tail_slot = jnp.mod(tail_pos, W)
    bidx = jnp.arange(B)[:, None]
    ck = cache["k"].at[bidx, tail_slot].set(k[:, S - Wp:].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, tail_slot].set(v[:, S - Wp:].astype(cache["v"].dtype))
    return out.reshape(B, S, -1) @ p["wo"], {"k": ck, "v": cv}
