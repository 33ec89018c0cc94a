"""Jit'd public wrapper for the flash-attention kernel."""

from __future__ import annotations

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import flash_attention_ref


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    bq=_k.DEFAULT_BQ, bk=_k.DEFAULT_BK, interpret=None):
    itp = resolve_interpret(interpret)
    return _k.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, bq=bq, bk=bk, interpret=itp)


__all__ = ["flash_attention", "flash_attention_ref"]
