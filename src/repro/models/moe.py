"""Mixture-of-Experts FFN (deepseek-v2-lite, olmoe).

Training runs GShard-style capacity dispatch, SPMD-shardable for expert
parallelism (:func:`moe_apply`).  Serving runs :func:`held_experts_apply`:
no capacity and no drop, so a token's output never depends on the other
tokens of its batch.  The layer holds experts ``0..n_experts_held-1`` (the
chip's share under expert parallelism), routes over all ``n_experts``, and
adds what its held experts give for every pick routed to them, through one
grouped matrix product (the ``expert_gmm`` Pallas kernel).

Dispatch uses the einsum/one-hot formulation (t5x/GShard lineage): tokens are
split into groups of ``group_size``; within each group every token picks
top-k experts, claims a capacity slot, and is dispatched/combined by two
einsums.  Under pjit with tokens sharded over ``data`` and the expert axis of
the weights sharded over ``model``, XLA SPMD emits the canonical all-to-all
pair — the collective the §Roofline analysis tracks for MoE cells.

The router state (expert assignments) is part of the layer's *combinational
logic* in the paper's language; no sequential state is carried, so MoE layers
drop into the layers-as-scan schedule unchanged.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.expert_gmm import ops as gmm_ops

from .config import ModelConfig
from .layers import dense_init, mlp_apply, mlp_params

PyTree = Any


def moe_params(key, cfg: ModelConfig) -> PyTree:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    Eh = cfg.experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),  # router kept f32
        "w_in": dense_init(ks[1], (Eh, D, F), cfg.p_dtype),
        "w_gate": dense_init(ks[2], (Eh, D, F), cfg.p_dtype),
        "w_out": dense_init(ks[3], (Eh, F, D), cfg.p_dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_params(
            ks[4], D, cfg.n_shared_experts * F, gated=True, dtype=cfg.p_dtype
        )
    return p


def _capacity(group_size: int, cfg: ModelConfig) -> int:
    cap = int(group_size * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, cfg.top_k)


def route(p, cfg: ModelConfig, x):
    """x: [..., D] → (top-k expert ids, weights, aux loss).  The logits are
    float32 at ``highest`` precision, as DeepSeek's gate computes them; the
    top-k weights are renormalised where ``norm_topk_prob`` says so."""
    logits = jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

    # Switch-style load-balance loss: E · Σ_e fraction_e · mean_prob_e
    E = cfg.n_experts
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=-2), axis=tuple(range(top_e.ndim - 1))
    ) / cfg.top_k
    pbar = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = E * jnp.sum(f * pbar)
    return top_e, top_w.astype(x.dtype), aux


def moe_apply(p, cfg: ModelConfig, x, group_size: int = 2048):
    """x: [B, S, D] → (y, aux_loss).  Capacity-based top-k dispatch."""
    if cfg.experts_held != cfg.n_experts:
        raise ValueError("moe_apply (training) needs every expert; "
                         f"this layer holds {cfg.experts_held} of {cfg.n_experts}")
    B, S, D = x.shape
    T = B * S
    g = min(group_size, T)
    while T % g:
        g //= 2
    G = T // g
    xt = x.reshape(G, g, D)

    top_e, top_w, aux = route(p, cfg, xt)        # [G,g,k] ids / weights
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(g, cfg)

    # Slot assignment: position of each (token, choice) within its expert's
    # queue, computed with a running count over the flattened (token-major)
    # choice order — deterministic, drop-beyond-capacity.
    e_onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)          # [G,g,k,E]
    flat = e_onehot.reshape(G, g * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                          # slots before me
    slot = jnp.sum(pos * flat, axis=-1).reshape(G, g, k)           # [G,g,k]
    keep = slot < C

    # dispatch/combine tensors, [G, g, E, C]; the k axis is contracted inside
    # the einsum (batched matmul) so the [g,k,E,C] outer product is never
    # materialized.
    slot_oh = jax.nn.one_hot(jnp.where(keep, slot, C), C, dtype=x.dtype)  # OOB→drop
    e_oh = e_onehot.astype(x.dtype)
    dispatch = jnp.einsum("Gtke,Gtkc->Gtec", e_oh, slot_oh)
    combine = jnp.einsum("Gtke,Gtkc->Gtec", e_oh * top_w[..., None], slot_oh)

    xe = jnp.einsum("Gtec,Gtd->Gecd", dispatch, xt)                # [G,E,C,D]
    h = jnp.einsum("Gecd,edf->Gecf", xe, p["w_in"])
    hg = jnp.einsum("Gecd,edf->Gecf", xe, p["w_gate"])
    h = jax.nn.silu(hg) * h
    ye = jnp.einsum("Gecf,efd->Gecd", h, p["w_out"])               # [G,E,C,D]
    y = jnp.einsum("Gtec,Gecd->Gtd", combine, ye)

    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], xt, act=cfg.mlp_act)

    return y.reshape(B, S, D), aux


def held_experts_apply(p, cfg: ModelConfig, x, layer=None):
    """x: [B, S, D] -> y.  The serving expert layer: the experts held here
    are ``0 .. n_held - 1`` of the router's ``n_experts`` (``p["w_in"]``
    holds their weights); every pick routed to one of them is computed,
    none is dropped, and picks of experts held elsewhere add nothing.  The
    shared experts are added for every token.  With ``layer`` the expert
    weights are the whole layer stack [G, n_held, ...], read in place by
    the kernel; without it they are one layer's.

    Under a device mesh (a sharded server traces its programs inside
    ``jax.set_mesh``) the layer runs as a ``shard_map``, since GSPMD cannot
    partition the Mosaic kernel: the held experts divide over ``model`` as
    the sharding rules lay out ``moe/w_*`` (expert parallelism), each device
    adds what its own experts give, and the parts are summed over
    ``model``."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    w = {n: p[n] for n in ("w_in", "w_gate", "w_out")}
    if layer is None:
        w, layer = jax.tree.map(lambda a: a[None], w), 0
    layer = jnp.asarray(layer, jnp.int32)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size > 1:
        y = _held_experts_on_mesh(mesh, p["router"], w, cfg, xt, layer)
    else:
        y = _held_experts(p["router"], w, cfg, xt, 0, layer)
    y = y.astype(x.dtype)
    if cfg.n_shared_experts:
        with jax.named_scope("shared_experts"):
            y = y + mlp_apply(p["shared"], xt, act=cfg.mlp_act)
    return y.reshape(B, S, D)


def _held_experts_on_mesh(mesh, router, w, cfg: ModelConfig, xt, layer):
    """:func:`_held_experts` as a ``shard_map`` over ``mesh``: experts over
    ``model`` (where it divides them), tokens over the data axes (where
    they divide the tokens), the rest replicated."""
    T, Eh = xt.shape[0], w["w_in"].shape[1]
    ep = mesh.shape.get("model", 1)
    ep_axis = "model" if ep > 1 and Eh % ep == 0 else None
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = math.prod(mesh.shape[a] for a in dp)
    tok = P(dp if dp and T % n_dp == 0 else None)

    def part(router, w, xt, layer):
        if ep_axis is None:
            return _held_experts(router, w, cfg, xt, 0, layer)
        first = jax.lax.axis_index(ep_axis) * (Eh // ep)
        return jax.lax.psum(_held_experts(router, w, cfg, xt, first, layer), ep_axis)

    return jax.shard_map(
        part, in_specs=(P(), P(None, ep_axis), tok, P()), out_specs=tok,
        check_vma=False)(router, w, xt, layer)


def _held_experts(router, w, cfg: ModelConfig, xt, first, layer):
    """xt: [T, D] -> [T, D] float32: what experts ``first .. first + n - 1``
    (``w``: stacks [G, n, ...], read at ``layer``) give for the picks
    routed to them."""
    D = xt.shape[-1]
    T, k, Eh = xt.shape[0], cfg.top_k, w["w_in"].shape[1]
    with jax.named_scope("router"):
        top_e, top_w, _ = route({"router": router}, cfg, xt)
    with jax.named_scope("experts"):
        # sort the T·k picks by held expert; the last group takes the picks
        # of experts held elsewhere and the padding to whole row tiles
        e = top_e.reshape(-1) - first
        group = jnp.where((e >= 0) & (e < Eh), e, Eh)
        order = jnp.argsort(group, stable=True)
        M = -(-T * k // gmm_ops.TM) * gmm_ops.TM
        sizes = jnp.bincount(group, length=Eh + 1).at[Eh].add(M - T * k).astype(jnp.int32)
        xs = jnp.take(xt, jnp.pad(order // k, (0, M - T * k)), axis=0)
        h = gmm_ops.expert_gmm(xs, w["w_in"], sizes, layer)
        g = gmm_ops.expert_gmm(xs, w["w_gate"], sizes, layer)
        ye = gmm_ops.expert_gmm(jax.nn.silu(g) * h, w["w_out"], sizes, layer)   # [M, D]
        # back to token order: pick j of token t sits at row inv[t·k + j]
        inv = jnp.argsort(order)
        ye = jnp.take(ye, inv, axis=0).reshape(T, k, D)
        return jnp.sum(top_w.astype(jnp.float32)[..., None] * ye, axis=1)
