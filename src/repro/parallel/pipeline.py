"""Pipeline parallelism = C-slow retiming across devices (paper §III-F).

The FPGA view: C-slowing a datapath lets C independent streams share it;
retiming then spreads the logic across pipeline registers.  Across devices,
the datapath is the layer stack split into P stages (one per device along
the ``stage`` mesh axis), the streams are C microbatches, and the pipeline
registers are the `lax.ppermute` transfers between neighbours.  Utilization
is the classic C·P / (P·(P+C−1)) — exactly `core.cslow.pipeline_utilization`.

Implemented with `shard_map` so the collective schedule (one
collective-permute per tick) is explicit in the lowered HLO — it shows up in
the §Roofline collective term and is validated in multi-device tests.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_apply(
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    stage_params: PyTree,   # leaves [P, ...] — one slice per stage
    microbatches: jnp.ndarray,  # [C, mb, ...]
    mesh: Mesh,
    axis_name: str = "stage",
):
    """Run ``microbatches`` through P chained stages, GPipe/C-slow schedule.

    Returns [C, mb, ...] outputs equal to sequentially applying all stages.
    """
    C = microbatches.shape[0]
    num_stages = mesh.shape[axis_name]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    )
    def run(params_local, mb):
        # params_local: [1, ...] slice for this stage
        params_here = jax.tree.map(lambda x: x[0], params_local)
        idx = jax.lax.axis_index(axis_name)
        right = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        # the carry is device-varying (each stage holds different data):
        # mark it so, or the scan's carry typing rejects the ppermute output
        buf = pcast(jnp.zeros_like(mb[0]), (axis_name,), to="varying")
        outs = pcast(jnp.zeros_like(mb), (axis_name,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use the buffer
            feed = jnp.where(t < C, t, 0)
            x_in = jnp.where(idx == 0, mb[feed], buf)
            y = stage_fn(params_here, x_in)
            # last stage retires microbatch t-(P-1)
            ret = t - (num_stages - 1)
            slot = jnp.clip(ret, 0, C - 1)
            live = (idx == num_stages - 1) & (ret >= 0) & (ret < C)
            outs = outs.at[slot].set(
                jnp.where(live, y.astype(outs.dtype), outs[slot])
            )
            buf = jax.lax.ppermute(y, axis_name, right)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(C + num_stages - 1)
        )
        # outputs live on the last stage only; psum broadcasts (zeros elsewhere)
        outs = jnp.where(idx == num_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis_name)

    return run(stage_params, microbatches)


def sequential_reference(stage_fn, stage_params, microbatches):
    """Oracle: apply the P stages in order to every microbatch."""
    num_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def one(x):
        for s in range(num_stages):
            ps = jax.tree.map(lambda p: p[s], stage_params)
            x = stage_fn(ps, x)
        return x

    return jax.vmap(one)(microbatches)
