"""Grouped matrix product of the held experts: the MoE expert layer's kernel.

Rows of ``lhs`` come sorted by expert.  ``group_sizes`` has one entry per
held expert and a last entry for the rows that belong to no held expert
(picks of experts held elsewhere, and padding).  Group ``e`` of the rows is
multiplied by ``rhs[e]``; the rows of the last group come out zero and cost
no work.  Tiles over the row axis follow the group boundaries
(``make_group_metadata``, the megablox scheme): a tile that straddles two
groups is visited once per group, and each visit stores only its own rows.

The Mosaic call is named ``expert_gmm``, so a profile finds it by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels.platform import vmem_limit

TM = 128                       # rows per tile
# elements of one (tk, tn) weight tile; double-buffered in f32 that is 8 MiB
TILE_ELEMS = 1 << 20


def _divisors128(dim: int) -> list[int]:
    """Tile sizes Mosaic accepts along ``dim``: multiples of 128 that divide
    it, and the whole dimension."""
    return sorted({t for t in range(128, dim, 128) if dim % t == 0} | {dim})


def tiling(k: int, n: int) -> tuple[int, int, int]:
    """The largest (tk, tn) weight tile within ``TILE_ELEMS``, ties to the
    wider ``tn``."""
    best = max(((tk, tn) for tk in _divisors128(k) for tn in _divisors128(n)
                if tk * tn <= TILE_ELEMS or (tk == 128 and tn == 128)),
               key=lambda t: (t[0] * t[1], t[1]))
    return TM, best[0], best[1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm(lhs, rhs, group_sizes, layer, *, interpret: bool = False):
    """``lhs`` [m, k] (m a multiple of 128), ``rhs`` [G, E, k, n], ``layer``
    an int32 scalar below G, ``group_sizes`` [E + 1] int32 summing to m ->
    [m, n] float32, the product with ``rhs[layer]``.

    The kernel reads its layer of the stack in place: a layer scan hands it
    the whole stack instead of a per-layer copy."""
    m, k = lhs.shape
    _, E, _, n = rhs.shape
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tm, tk, tn = tiling(k, n)
    if m % tm:
        raise ValueError(f"expert_gmm: {m} rows is not a multiple of {tm}")
    meta, num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.zeros((), jnp.int32),
        num_nonzero_groups=E, visit_empty_groups=False)
    tiles_k = k // tk

    def kernel(meta, _layer, lhs_ref, rhs_ref, out_ref, acc_ref):
        offsets, group_ids, m_tile_ids = meta
        t, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(ki == tiles_k - 1)
        def _store():
            g = group_ids[t]
            row = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + m_tile_ids[t] * tm
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])

    # grid (n tile, row-tile visit, k tile); scalar prefetch (meta, layer)
    lhs_map = lambda ni, t, ki, meta, layer: (meta[2][t], ki)
    rhs_map = lambda ni, t, ki, meta, layer: (layer[0], meta[1][t], ki, ni)
    out_map = lambda ni, t, ki, meta, layer: (meta[2][t], ni)

    it = jnp.dtype(lhs.dtype).itemsize
    blocks = 2 * (tm * tk * it + tk * tn * rhs.dtype.itemsize + tm * tn * 4) + tm * tn * 4
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blocks)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=m * k * it + E * k * n * rhs.dtype.itemsize + m * n * 4),
        interpret=interpret,
        name="expert_gmm",
    )(meta, layer, lhs, rhs)
    # rows of the last group, and tiles no held group visited, hold no result
    held = jnp.arange(m)[:, None] < meta[0][E]
    return jnp.where(held, out, 0.0)
