"""Shared neural layers (pure-functional JAX).

Parameters are plain nested dicts; sharding is attached later by path-based
rules (`repro.parallel.sharding`).  Everything here is jnp-only so that the
dry-run compiles on any backend; Pallas fast paths hook in at the call sites
in `attention.py` / `ssm.py`.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale_axis: int = 0):
    fan_in = shape[scale_axis]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_params(dim: int, dtype) -> PyTree:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_params(dim: int, dtype) -> PyTree:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (with partial-rotary + position offsets for decode)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, partial: float = 1.0) -> jnp.ndarray:
    rot = int(head_dim * partial)
    rot -= rot % 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched ``scale`` times
    (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_freqs(dim: int, theta: float, factor: float, orig_max_pos: int,
               beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN frequencies of the ``dim/2`` rotated pairs: pairs that turn more
    than ``beta_fast`` times over the original context keep their frequency,
    pairs that turn fewer than ``beta_slow`` times are slowed ``factor``-fold,
    and a linear ramp between the two correction dims blends the rest."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def correction_dim(rotations):
        return dim * math.log(orig_max_pos / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(inter * ramp + extra * (1.0 - ramp), jnp.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, partial: float = 1.0,
               *, freqs: jnp.ndarray | None = None, mscale: float = 1.0):
    """x: [..., S, H, hd]; positions: [..., S] (int).  Rotates the first
    ``partial * hd`` channels, passes the rest through (phi4-style).
    ``freqs`` replaces the plain RoPE frequencies (YaRN), and ``mscale``
    scales cos and sin."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta, partial)     # [rot/2]
    rot = freqs.shape[0] * 2
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, rot/2]
    cos = (jnp.cos(angles) * mscale)[..., :, None, :]   # [..., S, 1, rot/2]
    sin = (jnp.sin(angles) * mscale)[..., :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU/GeGLU or plain)
# ---------------------------------------------------------------------------

def mlp_params(key, d_model: int, d_ff: int, gated: bool, dtype) -> PyTree:
    ks = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_out": dense_init(ks[1], (d_ff, d_model), dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), dtype)
    return p


def _act(name: str):
    from repro.core.state_space import resolve_activation

    return resolve_activation(name)


def mlp_apply(params, x, act: str = "silu"):
    h = x @ params["w_in"]
    if "w_gate" in params:
        h = _act(act)(x @ params["w_gate"]) * h
    else:
        h = _act(act)(h)
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embedding_params(key, vocab: int, d_model: int, dtype) -> PyTree:
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x, table: jnp.ndarray | None = None):
    """Logits head; pass ``table`` for tied embeddings."""
    w = table if table is not None else params["w"]
    return x @ w.T if table is not None else x @ w
