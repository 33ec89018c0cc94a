"""Pallas TPU kernel: int8 MACC matmul with int32 accumulation.

The paper implements NN MACCs on DSP48E1 slices with wide accumulators
(§IV-B); the MXU's int8 path is the TPU equivalent.  Blocked [bm,bk]×[bk,bn]
with the K axis as a sequential grid dimension accumulating into an int32
VMEM scratch; scales are applied once at the final K step (requantization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _kernel(a_ref, b_ref, as_ref, bs_ref, o_ref, acc_scr, *, num_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.int32),  # Mosaic maps s8xs8->s32 onto the MXU
        b_ref[...].astype(jnp.int32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(ki == num_k - 1)
    def _fin():
        o_ref[...] = (
            acc_scr[...].astype(jnp.float32) * as_ref[...] * bs_ref[...]
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(a_q, b_q, a_scale, b_scale, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                bk=DEFAULT_BK, interpret: bool = False):
    M, K = a_q.shape
    _, N = b_q.shape
    bm = min(bm, M)
    while M % bm:
        bm //= 2
    bn = min(bn, N)
    while N % bn:
        bn //= 2
    bk = min(bk, K)
    while K % bk:
        bk //= 2
    num_k = K // bk

    out = pl.pallas_call(
        functools.partial(_kernel, num_k=num_k),
        grid=(M // bm, N // bn, num_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a_q, b_q, a_scale, b_scale)
    return out
