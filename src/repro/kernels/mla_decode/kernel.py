"""MLA's absorbed decode attention over the latent cache, one query token a
slot: each slot's live rows read once, for the score and the value.

The latent stacks stay in HBM.  For slot ``b`` the kernel visits row tiles
``0 .. pos[b] // tk`` only, moving each tile HBM -> VMEM once by a manual
double-buffered DMA (the next tile, or the next slot's first tile, is in
flight while one is computed); a tile serves both products under an online
softmax.  Rows past a slot's position cost no DMA and no loop step.  The
whole batch is one loop in one kernel invocation, so no grid step is spent
on a dead tile.

The kernel reads its layer of the stack in place, by a prefetched index, as
``expert_gmm`` reads its layer of the expert weights.  The Mosaic call is
named ``mla_decode``, so a profile finds it by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import vmem_bytes, vmem_limit

from .ref import NEG_INF

TK = 512                       # latent rows per tile


def tile_rows(s_max: int, block_rows: int = TK) -> int:
    """Rows of one tile of an ``s_max``-row cache: ``block_rows``, or the
    whole cache where it is shorter."""
    return min(block_rows, s_max)


def slot_tiles(pos, s_max: int, block_rows: int = TK):
    """Tiles the kernel reads for a query at ``pos`` (any array shape)."""
    return pos // tile_rows(s_max, block_rows) + 1


@functools.partial(jax.jit, static_argnames=("scale", "block_rows", "interpret"))
def mla_decode(q_lat, q_rope, c_kv, k_rope, layer, pos, *, scale: float,
               block_rows: int = TK, interpret: bool = False):
    """``q_lat`` [B, H, r], ``q_rope`` [B, H, dr], stacks ``c_kv``
    [G, B, S, r] and ``k_rope`` [G, B, S, dr], ``layer`` an int32 scalar
    below G, ``pos`` [B] int32 below S -> ``o_lat`` [B, H, r] float32:
    for each slot, softmax over rows ``0 .. pos[b]`` of ``scale ·
    (q_lat·c_kv + q_rope·k_rope)``, applied to ``c_kv``.  Products take the
    operands as given at the default precision and accumulate in f32; the
    softmax is f32."""
    B, H, r = q_lat.shape
    dr = q_rope.shape[-1]
    S = c_kv.shape[2]
    tk = tile_rows(S, block_rows)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    pos = jnp.asarray(pos, jnp.int32)
    # A TPU lays out an f32 [..., S, dr] array with dr < 128 with S on the
    # lanes: [..., dr, S] is the stack as it lies in HBM (a bitcast, no
    # copy), and its tiles are [dr, tk]
    kr_t = jnp.swapaxes(k_rope, 2, 3)

    def kernel(layer_ref, pos_ref, ql_ref, qr_ref, c_hbm, kr_hbm, o_ref, c_buf, kr_buf, sem):
        g = layer_ref[0]

        def copies(b, i, slot):
            # the last tile of a cache that tk does not divide starts early;
            # its rows before i·tk are masked
            start = jnp.minimum(i * tk, S - tk)
            return (pltpu.make_async_copy(c_hbm.at[g, b, pl.ds(start, tk)],
                                          c_buf.at[slot], sem.at[0, slot]),
                    pltpu.make_async_copy(kr_hbm.at[g, b, :, pl.ds(start, tk)],
                                          kr_buf.at[slot], sem.at[1, slot]))

        for cp in copies(0, 0, 0):
            cp.start()

        def one_slot(b, slot):
            p = pos_ref[b]
            n = p // tk + 1
            ql, qr = ql_ref[b], qr_ref[b]

            def one_tile(i, carry):
                slot, m, l, acc = carry
                last = i + 1 == n
                nb, ni = jnp.where(last, b + 1, b), jnp.where(last, 0, i + 1)

                @pl.when(nb < B)
                def _prefetch():
                    for cp in copies(nb, ni, 1 - slot):
                        cp.start()

                for cp in copies(b, i, slot):
                    cp.wait()
                c, kr = c_buf[slot], kr_buf[slot]
                s = (jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                     + jnp.dot(qr, kr, preferred_element_type=jnp.float32))
                row = jnp.minimum(i * tk, S - tk) + jax.lax.broadcasted_iota(
                    jnp.int32, (H, tk), 1)
                s = jnp.where((row >= i * tk) & (row <= p), s * scale, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                e = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(e, axis=1, keepdims=True)
                acc = alpha * acc + jnp.dot(e, c, preferred_element_type=jnp.float32)
                return 1 - slot, m_new, l, acc

            init = (slot, jnp.full((H, 1), -jnp.inf, jnp.float32),
                    jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, r), jnp.float32))
            slot, _, l, acc = jax.lax.fori_loop(0, n, one_tile, init)
            o_ref[b] = acc / l
            return slot

        jax.lax.fori_loop(0, B, one_slot, 0)

    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    it = c_kv.dtype.itemsize
    blocks = (2 * (vmem_bytes((B, H, r), q_lat.dtype) + vmem_bytes((B, H, dr), q_rope.dtype)
                   + vmem_bytes((B, H, r), jnp.float32))
              + 2 * (vmem_bytes((tk, r), c_kv.dtype) + vmem_bytes((dr, tk), k_rope.dtype)))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[whole(B, H, r), whole(B, H, dr),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole(B, H, r),
            grid=(1,),
            scratch_shapes=[pltpu.VMEM((2, tk, r), c_kv.dtype),
                            pltpu.VMEM((2, dr, tk), k_rope.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(blocks)),
        # an upper bound: every row of every slot
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * S * (2 * r + dr), transcendentals=B * H * S,
            bytes_accessed=B * S * (r + dr) * it + 2 * B * H * r * 4),
        interpret=interpret,
        name="mla_decode",
    )(layer, pos, q_lat, q_rope, c_kv, kr_t)
