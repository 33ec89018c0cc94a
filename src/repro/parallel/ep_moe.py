"""Explicit expert-parallel MoE dispatch (shard_map + lax.all_to_all).

The pjit path (`models.moe.moe_apply`) leaves communication to the SPMD
partitioner.  This is the hand-scheduled alternative used at scale: tokens
AND experts shard over the same ``ep`` axis; each rank routes its local
tokens, packs per-expert slot buffers, and exactly **two all_to_alls per MoE
layer** (dispatch + return) move token slots to/from the expert owners — a
fixed, auditable collective schedule.

In the production mesh this runs over the "model" axis with the sequence
dim sharded onto it (the SP layout §Perf cell 2 establishes); the
equivalence test drives it on a dedicated 8-way axis.

Capacity per (rank, expert) = max(ceil(T_local·k·cf/E), k); overflow drops,
matching `moe_apply` with group == local shard.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import ModelConfig

PyTree = Any


def _route_local(p, cfg: ModelConfig, x):
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return top_e, top_w.astype(x.dtype)


def ep_moe_apply(p, cfg: ModelConfig, x, mesh: Mesh, *, axis: str = "model"):
    """x: [T, D] tokens (global), sharded over ``axis``; expert weights
    [E, ...] sharded over ``axis``.  Returns y: [T, D]."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    ep = mesh.shape[axis]
    E_local = E // ep

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            {"router": P(), "w_in": P(axis), "w_gate": P(axis), "w_out": P(axis)},
            P(axis, None),
        ),
        out_specs=P(axis, None),
    )
    def run(pw, xt):
        T_local = xt.shape[0]
        C = max(int(T_local * k * cfg.capacity_factor / E), k)

        top_e, top_w = _route_local(pw, cfg, xt)            # [T,k]
        e_oh = jax.nn.one_hot(top_e, E, dtype=jnp.int32)
        flat = e_oh.reshape(T_local * k, E)
        pos = jnp.cumsum(flat, axis=0) - flat
        slot = jnp.sum(pos * flat, -1).reshape(T_local, k)
        keep = slot < C

        # pack send buffer [E, C, D]: slot (e, c) holds one token's content
        tok_idx = jnp.broadcast_to(jnp.arange(T_local)[:, None], (T_local, k))
        e_flat = jnp.where(keep, top_e, 0).reshape(-1)
        s_flat = jnp.where(keep, slot, C - 1).reshape(-1)
        vals = jnp.where(keep.reshape(-1)[:, None], xt[tok_idx.reshape(-1)], 0.0)
        send = jnp.zeros((E, C, D), xt.dtype).at[e_flat, s_flat].add(vals)

        # dispatch: rank r receives, for ITS experts, every rank's slots
        recv = jax.lax.all_to_all(
            send.reshape(ep, E_local, C, D), axis, 0, 0
        )                                                   # [ep, E_local, C, D]
        recv = jnp.moveaxis(recv, 0, 1).reshape(E_local, ep * C, D)

        # local expert FFN on owned experts
        h = jnp.einsum("ecd,edf->ecf", recv, pw["w_in"])
        hg = jnp.einsum("ecd,edf->ecf", recv, pw["w_gate"])
        y_e = jax.nn.silu(hg) * h
        y_e = jnp.einsum("ecf,efd->ecd", y_e, pw["w_out"])   # [E_local, ep*C, D]

        # return trip: give each source rank back its slots
        back = jnp.moveaxis(y_e.reshape(E_local, ep, C, D), 1, 0)
        back = jax.lax.all_to_all(back, axis, 0, 0)          # [ep, E_local, C, D]
        back = back.reshape(E, C, D)

        # combine on the owning rank
        g = back[e_flat, s_flat].reshape(T_local, k, D)
        g = jnp.where(keep[..., None], g, 0.0)
        return jnp.sum(g * top_w[..., None], axis=1)

    pw = {"router": p["router"], "w_in": p["w_in"], "w_gate": p["w_gate"],
          "w_out": p["w_out"]}
    y = run(pw, x)

    if cfg.n_shared_experts:
        from repro.models.layers import mlp_apply

        y = y + mlp_apply(p["shared"], x, act=cfg.mlp_act)
    return y


def ep_moe_reference(p, cfg: ModelConfig, x):
    """Dense oracle with the same per-rank capacity semantics is provided by
    `models.moe.moe_apply` with group_size == T_local; tests use it."""
    raise NotImplementedError("use models.moe.moe_apply as the oracle")
