"""Pallas TPU kernel: fused LSTM cell — the recurrent datapath in VMEM.

One grid step processes a [bb, ct] (batch-block × time-chunk) tile.  Per
step the four gate pre-activations are ONE [bb, D+H] × [D+H, 4H] MXU
contraction (input and hidden matmuls fused by concatenation — the paper's
single shared MACC array serving all four gates), sigmoid/tanh are applied
in-VMEM on the VPU, and the ``(h, c)`` carry lives in VMEM scratch that
persists across the sequential chunk axis — the state register of the
paper's eq. 1 datapath, never spilled to HBM between chunks.

Grid: (Bp/bb, Tp/ct); batch parallel, chunk axis "arbitrary" (sequential)
so the carry scratch is live across chunks.  Ragged ``Bsz``/``T`` are
zero-padded up to the block/chunk multiple and the padded tail steps are
masked out of the carry update, so a prime prompt length keeps the full
``ct``-step tile (Mosaic refuses blocks whose last two dims are neither
multiples of (8, 128) nor the full array).  Within a chunk the steps are a
loop over time-major ``[ct, bb, ·]`` blocks.  The ``[D+H, 4H]`` weight is
resident and single-buffered; the kernel asks for the scoped VMEM its
blocks add up to (32 MiB of f32 weight at D=H=1024).

Quantized path (paper §IV-B): ``lut`` switches the gate activations to the
ROM-LUT idiom of ``kernels.tanh_lut`` — one-hot × table MXU contractions
with linear interpolation; σ(x) = (1 + tanh(x/2)) / 2 reuses the same table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._lut import lut_interpolate, shifted_table
from repro.kernels.platform import vmem_bytes, vmem_limit

DEFAULT_CHUNK = 32
DEFAULT_BLOCK_B = 8


def _make_acts(lut_refs, n_lut: int):
    if n_lut:
        lut = lut_refs[0][0, :]
        lut1 = lut_refs[1][0, :]
        tanh = lambda v: lut_interpolate(v, lut, lut1, n_lut)
    else:
        tanh = jnp.tanh
    sig = lambda v: 0.5 * (1.0 + tanh(0.5 * v))
    return tanh, sig


def _lstm_kernel(x_ref, W_ref, b_ref, h0_ref, c0_ref, *rest,
                 ct: int, H: int, last_chunk: int, t_total: int, n_lut: int):
    lut_refs, (y_ref, hout_ref, cout_ref), (h_scr, c_scr) = (
        rest[: 2 if n_lut else 0], rest[-5:-2], rest[-2:]
    )
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    tanh, sig = _make_acts(lut_refs, n_lut)
    ragged = (last_chunk + 1) * ct != t_total

    def step(t, carry):
        h, c = carry                                      # [bb, H] f32
        xt = x_ref[t].astype(jnp.float32)                 # [bb, D]
        W = W_ref[...].astype(jnp.float32)                # [D+H, 4H]
        z = jnp.concatenate([xt, h], axis=-1) @ W + b_ref[...].astype(jnp.float32)
        i_g = sig(z[:, :H])
        f_g = sig(z[:, H : 2 * H])
        g_g = tanh(z[:, 2 * H : 3 * H])
        o_g = sig(z[:, 3 * H :])
        c_new = f_g * c + i_g * g_g
        h_new = o_g * tanh(c_new)
        y_ref[t] = h_new.astype(y_ref.dtype)
        if ragged:    # padded tail steps must not advance the carry
            valid = ci * ct + t < t_total
            h_new = jnp.where(valid, h_new, h)
            c_new = jnp.where(valid, c_new, c)
        return h_new, c_new

    h, c = jax.lax.fori_loop(0, ct, step, (h_scr[...], c_scr[...]))
    h_scr[...] = h
    c_scr[...] = c

    @pl.when(ci == last_chunk)
    def _fin():
        hout_ref[...] = h
        cout_ref[...] = c


@functools.partial(jax.jit, static_argnames=("chunk", "block_b", "interpret"))
def lstm_seq(x, w_x, w_h, b, h0, c0, lut=None, *, chunk: int = DEFAULT_CHUNK,
             block_b: int = DEFAULT_BLOCK_B, interpret: bool = False):
    """Fused-cell LSTM over a sequence.  Shapes as in ``ref.lstm_seq_ref``."""
    Bsz, T, D = x.shape
    H = w_h.shape[0]
    ct = min(chunk, T)
    bb = min(block_b, Bsz)
    Tp = -(-T // ct) * ct
    Bp = -(-Bsz // bb) * bb

    W = jnp.concatenate([w_x, w_h], axis=0)  # [D+H, 4H]
    n_lut = 0 if lut is None else lut.shape[0]
    x_tm = jnp.pad(jnp.swapaxes(x, 0, 1), ((0, Tp - T), (0, Bp - Bsz), (0, 0)))
    pad_b = lambda a: jnp.pad(a, ((0, Bp - Bsz), (0, 0)))

    grid = (Bp // bb, Tp // ct)
    kernel = functools.partial(
        _lstm_kernel, ct=ct, H=H, last_chunk=Tp // ct - 1, t_total=T,
        n_lut=n_lut)

    once = dict(pipeline_mode=pl.Buffered(1))   # grid-invariant: one buffer
    in_specs = [
        pl.BlockSpec((ct, bb, D), lambda i, c: (c, i, 0)),        # x
        pl.BlockSpec((D + H, 4 * H), lambda i, c: (0, 0), **once),  # W
        pl.BlockSpec((1, 4 * H), lambda i, c: (0, 0), **once),    # b
        pl.BlockSpec((bb, H), lambda i, c: (i, 0)),               # h0
        pl.BlockSpec((bb, H), lambda i, c: (i, 0)),               # c0
    ]
    operands = [x_tm, W, b[None], pad_b(h0), pad_b(c0)]
    vmem = (vmem_bytes(W.shape, W.dtype) + vmem_bytes((1, 4 * H), b.dtype)
            + 2 * vmem_bytes((ct, bb, D), x.dtype)        # x, double-buffered
            + 2 * vmem_bytes((ct, bb, H), x.dtype)        # y, double-buffered
            + 10 * vmem_bytes((bb, H), jnp.float32))      # h0 c0 h c outs, scratch
    if n_lut:
        lut1 = shifted_table(lut)
        in_specs += [
            pl.BlockSpec((1, n_lut), lambda i, c: (0, 0), **once),  # lut
            pl.BlockSpec((1, n_lut), lambda i, c: (0, 0), **once),  # shifted
        ]
        operands += [lut[None].astype(jnp.float32), lut1[None].astype(jnp.float32)]
        vmem += 2 * vmem_bytes((1, n_lut), jnp.float32)

    y, h_final, c_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((ct, bb, H), lambda i, c: (c, i, 0)),    # y
            pl.BlockSpec((bb, H), lambda i, c: (i, 0)),           # h_final
            pl.BlockSpec((bb, H), lambda i, c: (i, 0)),           # c_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, Bp, H), x.dtype),
            jax.ShapeDtypeStruct((Bp, H), jnp.float32),
            jax.ShapeDtypeStruct((Bp, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, H), jnp.float32),
            pltpu.VMEM((bb, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(vmem),
        ),
        interpret=interpret,
    )(*operands)
    return jnp.swapaxes(y[:T, :Bsz], 0, 1), h_final[:Bsz], c_final[:Bsz]
