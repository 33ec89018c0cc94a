"""Jit'd wrapper for the LUT-tanh kernel."""

from __future__ import annotations

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import make_lut, tanh_lut_ref


def tanh_lut(x, lut, *, block=_k.DEFAULT_BLOCK, interpret=None):
    itp = resolve_interpret(interpret)
    return _k.tanh_lut(x, lut, block=block, interpret=itp)


__all__ = ["tanh_lut", "tanh_lut_ref", "make_lut"]
