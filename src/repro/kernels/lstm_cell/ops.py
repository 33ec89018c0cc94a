"""Jit'd public wrapper for the fused LSTM cell kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import lstm_seq_lut_ref, lstm_seq_ref


def lstm_seq(x, w_x, w_h, b, h0=None, c0=None, lut=None, *,
             chunk: int = _k.DEFAULT_CHUNK, block_b: int = _k.DEFAULT_BLOCK_B,
             interpret: bool | None = None):
    """y, h_final, c_final = fused LSTM over x [Bsz, T, D].

    Unlike ``ssm_scan`` the carry is an explicit kernel input, so prefill
    resume and cache-seeded continuation use the same path as fresh starts.
    ``lut`` (a tanh table from ``tanh_lut.make_lut``) selects the quantized
    ROM-LUT gate activations.
    """
    Bsz, _, _ = x.shape
    H = w_h.shape[0]
    if h0 is None:
        h0 = jnp.zeros((Bsz, H), jnp.float32)
    if c0 is None:
        c0 = jnp.zeros((Bsz, H), jnp.float32)
    itp = resolve_interpret(interpret)
    return _k.lstm_seq(x, w_x, w_h, b, h0, c0, lut, chunk=chunk,
                       block_b=block_b, interpret=itp)


__all__ = ["lstm_seq", "lstm_seq_ref", "lstm_seq_lut_ref"]
