"""Jit'd public wrapper for the chunked selective-scan kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import ssm_scan_ref


def _maybe_nonzero(h0) -> bool:
    """True unless ``h0`` is concretely all-zero.  Under jit tracing the
    value is abstract — treat it as potentially nonzero (the ref path is
    identical math, so correctness never depends on guessing right)."""
    try:
        return bool((jnp.abs(h0) > 0).any())
    except Exception:  # noqa: BLE001 — TracerBoolConversionError and friends
        return True


def ssm_scan(x, delta, A, B, C, h0=None, *, chunk: int = _k.DEFAULT_CHUNK,
             block_d: int = _k.DEFAULT_BLOCK_D, w: int = _k.DEFAULT_W,
             interpret: bool | None = None):
    """y, h_final = chunked selective scan (see kernel.py for the math).

    The Pallas kernel has no h0 input; a resumed carry (chunked prefill /
    decode splice) automatically falls back to the jnp ref path instead of
    raising, so callers never need to special-case resumption.
    """
    if h0 is not None and _maybe_nonzero(h0):
        return ssm_scan_ref(x, delta, A, B, C, h0)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2], B.shape[-1]), jnp.float32)
    itp = resolve_interpret(interpret)
    return _k.ssm_scan(x, delta, A, B, C, h0, chunk=chunk, block_d=block_d,
                       w=w, interpret=itp)


__all__ = ["ssm_scan", "ssm_scan_ref"]
