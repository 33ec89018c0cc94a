"""Pallas TPU kernel: ROM-LUT activation with linear interpolation.

The paper stores offline-quantized tanh samples in FPGA block-RAM (§IV-B).
On TPU there is no scalar ROM port; the idiomatic translation keeps the LUT
resident in VMEM and performs the gather as a **one-hot × table matmul** on
the MXU (dynamic per-lane gathers don't vectorize on the VPU; one-hot
contraction is the standard trick).  Linear interpolation uses a second
contraction against the shifted table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._lut import lut_interpolate, shifted_table

DEFAULT_BLOCK = 1024


def _kernel(x_ref, lut_ref, lut1_ref, o_ref, *, n):
    x = x_ref[...].astype(jnp.float32)          # [1, bs]
    y = lut_interpolate(x[0], lut_ref[0, :], lut1_ref[0, :], n)
    o_ref[...] = y[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def tanh_lut(x, lut, *, block: int = DEFAULT_BLOCK, interpret: bool = False):
    """x: any shape; lut: [n] f32 (n a power of two)."""
    shape = x.shape
    flat = x.reshape(1, -1)
    S = flat.shape[1]
    bs = min(block, S)
    while S % bs:
        bs //= 2
    n = lut.shape[0]
    lut1 = shifted_table(lut)

    out = pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=(S // bs,),
        in_specs=[
            pl.BlockSpec((1, bs), lambda i: (0, i)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, S), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(flat, lut[None], lut1[None])
    return out.reshape(shape)
