"""Jit'd wrappers: raw int8 matmul + float->int8 quantized matmul."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import int8_matmul_ref, quantize_matmul_ref


def int8_matmul(a_q, b_q, a_scale, b_scale, *, interpret=None, **kw):
    itp = resolve_interpret(interpret)
    return _k.int8_matmul(a_q, b_q, a_scale, b_scale, interpret=itp, **kw)


def quantize_per_channel(w, axis: int = -2):
    """Symmetric int8 per-channel quantization of a weight ROM.

    ``axis`` is the contraction axis (reduced by the matmul): a ``[in, out]``
    matrix with ``axis=-2`` gets one scale per output channel — the paper's
    per-coefficient-bank fixed-point format.  Returns ``(w_q int8, scale
    f32)`` with ``scale`` keeping the reduced axis as size 1 so
    ``w_q * scale ≈ w`` broadcasts.  Shared with the generated Pallas kernel
    (codegen's fixed-point gate contraction) so both MACC paths round the
    same way.
    """
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def quantize_rows(a):
    """Symmetric int8 per-row activation quantization: ``(a_q, scale)`` with
    scale shaped ``[..., 1]``.  Pure jnp — usable inside kernel bodies."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def quantized_matmul(a, b, *, interpret=None, **kw):
    """Float API: per-row(M)/per-col(N) symmetric int8, int32 MACC."""
    a_q, a_s = quantize_rows(a)
    b_q, b_s = quantize_per_channel(b, axis=0)
    return int8_matmul(a_q, b_q, a_s, b_s, interpret=interpret, **kw)


__all__ = ["int8_matmul", "quantized_matmul", "int8_matmul_ref",
           "quantize_matmul_ref", "quantize_per_channel", "quantize_rows"]
