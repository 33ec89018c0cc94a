"""jnp oracle of the held-expert grouped product."""

from __future__ import annotations

import jax.numpy as jnp


def expert_gmm_ref(lhs, rhs, group_sizes, layer):
    """Row ``r`` of group ``e < E`` is ``lhs[r] @ rhs[layer, e]``; rows of
    the last group are zero."""
    rhs = rhs[layer]
    E = rhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for e in range(E):
        out = out + jnp.where((gid == e)[:, None], lhs @ rhs[e], 0.0)
    return out
