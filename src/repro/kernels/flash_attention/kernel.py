"""Pallas TPU kernel: blocked online-softmax attention (FlashAttention),
with causal masking, sliding windows, GQA head grouping, and logit softcap.

TPU adaptation notes (vs the CUDA original):
  * the KV loop is a **sequential grid dimension** with VMEM scratch
    carrying (m, l, acc) — Mosaic keeps the scratch resident across the
    ``arbitrary`` axis, which is the TPU idiom for the CUDA inner loop;
  * block shapes default to (128, 128): MXU-aligned on both the q and k
    tiles; head_dim rides the lane dimension (padded if not 128);
  * GQA is expressed in the k/v BlockSpec index_map (kv_head = h // group)
    — no KV replication is materialized in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale, causal, window, softcap, bq, bk, num_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :].astype(jnp.float32)   # [bq, hd]
    k = k_ref[0, :, 0, :].astype(jnp.float32)   # [bk, hd]
    v = v_ref[0, :, 0, :].astype(jnp.float32)   # [bk, hd]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0]                         # [bq]
    l_prev = l_scr[:, 0]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ()))
    )
    m_scr[:, 0] = m_new
    l_scr[:, 0] = l_new

    @pl.when(ki == num_k - 1)
    def _fin():
        l = l_scr[:, 0]
        # fully-masked rows (l == 0) normalize to 0, not NaN
        denom = jnp.where(l == 0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = False):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(bq, S)
    while S % bq:
        bq //= 2
    bk = min(bk, T)
    while T % bk:
        bk //= 2
    num_k = T // bk

    grid = (B, H, S // bq, num_k)
    kernel = functools.partial(
        _flash_kernel, scale=hd ** -0.5, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, num_k=num_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, qi, ki: (b, ki, h // group, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, qi, ki: (b, ki, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out
