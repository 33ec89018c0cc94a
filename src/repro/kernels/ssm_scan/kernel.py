"""Pallas TPU kernel: chunked selective scan — the paper's j-step Φ trick.

The serial recurrence h[t] = a_t h[t-1] + b_t is restructured exactly as
§II-C prescribes: within a **sub-block** of w steps, all pairwise transition
products Φ_{t,s} = exp(Σ_{r=s+1..t} Δ_r A) are formed in parallel (they are
differences of a cumulative log-decay, always ≤ 0 ⇒ exp ≤ 1, numerically
safe with no 1/Φ anywhere), turning w serial steps into one [w,w] masked
contraction; sub-blocks then chain through a single VMEM-resident carry.
The serial chain shrinks T → T/w — Fig. 3 in kernel form.

Grid: (Bsz, D/bd, T/ct) with the chunk axis sequential ("arbitrary") so the
carry scratch persists across chunks; (batch, channel) axes parallel.
VMEM per step: x/Δ blocks [ct, bd], B/C blocks [ct, N], carry [bd, N],
pairwise tensor [w, w, bd·N/lane] — sized for ~2-4 MB at the defaults
(ct=128, bd=128, N=16, w=8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



DEFAULT_CHUNK = 128
DEFAULT_BLOCK_D = 128
DEFAULT_W = 8


def _ssm_kernel(x_ref, d_ref, A_ref, B_ref, C_ref, y_ref, hout_ref, h_scr,
                *, w: int, ct: int, last_chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = A_ref[...]                       # [bd, N]
    h = h_scr[...]                       # [bd, N] f32

    ys = []
    for s in range(ct // w):             # static unroll: sub-blocks of w
        sl = slice(s * w, (s + 1) * w)
        xs = x_ref[0, sl, :].astype(jnp.float32)      # [w, bd]
        ds = d_ref[0, sl, :].astype(jnp.float32)      # [w, bd]
        Bs = B_ref[0, sl, :].astype(jnp.float32)      # [w, N]
        Cs = C_ref[0, sl, :].astype(jnp.float32)      # [w, N]

        la = ds[:, :, None] * A[None]                 # [w, bd, N] (≤ 0)
        L = jnp.cumsum(la, axis=0)                    # cumulative log-Φ
        # pairwise Φ: exp(L_t - L_s) for s <= t (differences ≤ 0 — safe);
        # mask the s > t half BEFORE exp (it is ≥ 0 and would inf→NaN).
        pair = L[:, None] - L[None, :]                # [w, w, bd, N]
        tsel = jnp.tril(jnp.ones((w, w), bool))
        phi = jnp.exp(jnp.where(tsel[:, :, None, None], pair, -jnp.inf))
        drive = (ds * xs)[:, :, None] * Bs[:, None, :]  # [w, bd, N]
        # contrib[t] = Σ_{s<=t} Φ_{t,s} drive_s   (the j-step contraction)
        contrib = jnp.einsum("tsdn,sdn->tdn", phi, drive)
        h_t = contrib + jnp.exp(L) * h[None]          # [w, bd, N]
        y = jnp.einsum("tdn,tn->td", h_t, Cs)         # [w, bd]
        ys.append(y)
        h = h_t[-1]

    y_ref[0, :, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
    h_scr[...] = h

    @pl.when(ci == last_chunk)
    def _fin():
        hout_ref[0, :, :] = h.astype(hout_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "block_d", "w", "interpret"),
)
def ssm_scan(x, delta, A, B, C, h0, *, chunk: int = DEFAULT_CHUNK,
             block_d: int = DEFAULT_BLOCK_D, w: int = DEFAULT_W,
             interpret: bool = False):
    """Chunked selective scan.  Shapes as in ``ref.ssm_scan_ref``.

    ``h0`` must currently be zeros (cache-seeded decode uses the single-step
    path); asserted in ops.py.
    """
    Bsz, T, D = x.shape
    N = B.shape[-1]
    ct = min(chunk, T)
    while T % ct:
        ct //= 2
    bd = min(block_d, D)
    while D % bd:
        bd //= 2
    ww = min(w, ct)

    grid = (Bsz, D // bd, T // ct)
    kernel = functools.partial(_ssm_kernel, w=ww, ct=ct, last_chunk=T // ct - 1)

    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, bd), lambda b, d, c: (b, c, d)),   # x
            pl.BlockSpec((1, ct, bd), lambda b, d, c: (b, c, d)),   # delta
            pl.BlockSpec((bd, N), lambda b, d, c: (d, 0)),          # A
            pl.BlockSpec((1, ct, N), lambda b, d, c: (b, c, 0)),    # B
            pl.BlockSpec((1, ct, N), lambda b, d, c: (b, c, 0)),    # C
        ],
        out_specs=[
            pl.BlockSpec((1, ct, bd), lambda b, d, c: (b, c, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, c: (b, d, 0)),    # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, T, D), x.dtype),
            jax.ShapeDtypeStruct((Bsz, D, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, delta, A, B, C)
    del h0  # zeros by contract; folded into the scratch init
    return y, h_final
