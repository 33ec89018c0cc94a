"""Sharding rules: DP/FSDP/TP/EP/SP plans for every architecture.

Philosophy (MaxText-style, path-regex rules): parameters are plain pytrees;
rules map parameter *paths* to PartitionSpecs over the production mesh

    single pod : ("data", "model")            = (16, 16)
    multi pod  : ("pod", "data", "model")     = (2, 16, 16)

Conventions
-----------
* batch/DP: activations shard batch over ``("pod","data")`` (all DP axes).
* FSDP/ZeRO: parameters and AdamW moments shard one non-TP dim over
  ``"data"`` (intra-pod ZeRO-3; the per-layer all-gather happens inside the
  layers-as-scan body, where XLA's latency-hiding scheduler overlaps it with
  the previous group's compute).  Gradients reduce over ``"pod"`` (plain DP
  across pods — cheaper than cross-pod FSDP on DCI links).
* TP: attention head dims / FFN hidden dims shard over ``"model"``
  (Megatron column/row pattern); MoE experts shard over ``"model"`` (EP);
  Mamba inner channels shard over ``"model"``.
* Every rule passes through a **divisibility guard**: an axis that does not
  divide the dimension is dropped (e.g. smollm's 9 heads on a 16-way model
  axis ⇒ attention falls back to replicated-over-model, FFN TP stays).
"""

from __future__ import annotations

import re
from typing import TYPE_CHECKING, Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

if TYPE_CHECKING:  # type-only; avoids a models<->parallel import cycle
    from repro.models.config import ModelConfig, ShapeSpec

PyTree = Any


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axis(mesh: Mesh) -> str | None:
    return "data" if "data" in mesh.axis_names else None


# ---------------------------------------------------------------------------
# divisibility guard
# ---------------------------------------------------------------------------

def _guard(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axes that don't divide their dim, exceed rank, or repeat."""
    out = []
    used: set[str] = set()
    for i, entry in enumerate(spec):
        if i >= len(shape) or entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if any(a in used for a in axes):
            out.append(None)
            continue
        total = int(np.prod([mesh.shape[a] for a in axes]))
        if shape[i] % total == 0:
            out.append(entry)
            used.update(axes)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def _param_rules(cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = True):
    """Ordered (regex, spec) rules.  'G' in comments = stacked group axis.

    ``fsdp=False`` drops the "data" factor from every rule (parameters
    replicate over DP, TP factors stay) — the *serving* layout, where the
    data axis carries decode slots and an FSDP all-gather per tick would
    dwarf the decode step it feeds.
    """
    f = fsdp_axis(mesh) if fsdp else None
    tp = None if cfg.pure_dp else "model"
    atp = tp if cfg.attn_tp else None
    return [
        # --- embeddings / head ---
        (r"embed/table$",            P(tp, f)),            # [V, D]
        (r"embed/proj$",             P(None, f)),          # [F_in, D] (encoder stub)
        (r"head/w$",                 P(f, tp)),            # [D, V]
        # --- attention (stacked [G, ...] unless shared) ---
        (r"shared/attn/w[qkv]$",     P(f, atp)),
        (r"shared/attn/wo$",         P(atp, f)),
        (r".*(attn|cross)/w[qkv]$",  P(None, f, atp)),     # [G, D, H*hd]
        (r".*(attn|cross)/wo$",      P(None, atp, f)),     # [G, H*hd, D]
        (r".*lora/[qkv]A$",          P(None, f, None)),
        (r".*lora/[qkv]B$",          P(None, None, atp)),
        # --- MLA ---
        (r".*attn/w_dkv$",           P(None, f, None)),    # [G, D, r]
        (r".*attn/w_krope$",         P(None, f, None)),
        (r".*attn/w_uk$",            P(None, None, atp)),  # [G, r, H*dn]
        (r".*attn/w_uv$",            P(None, None, atp)),
        # --- dense MLP ---
        (r"shared/mlp/w_(in|gate)$", P(f, tp)),
        (r"shared/mlp/w_out$",       P(tp, f)),
        (r".*mlp/w_(in|gate)$",      P(None, f, tp)),      # [G, D, F]
        (r".*mlp/w_out$",            P(None, tp, f)),      # [G, F, D]
        # --- MoE (EP over experts) ---
        (r".*moe/router$",           P(None, f, None)),    # [G, D, E]
        (r".*moe/w_(in|gate)$",      P(None, tp, f, None)),# [G, E, D, F]
        (r".*moe/w_out$",            P(None, tp, None, f)),# [G, E, F, D]
        (r".*moe/shared/w_(in|gate)$", P(None, f, tp)),
        (r".*moe/shared/w_out$",     P(None, tp, f)),
        # --- Mamba (split-aligned projections; see models/ssm.py §Perf note) ---
        (r".*mamba/w_[xz]$",         P(None, f, tp)),      # [G, D, DI]
        (r".*mamba/w_bc$",           P(None, f, None)),    # [G, D, 2N] (tiny)
        (r".*mamba/w_dt$",           P(None, f, None)),    # [G, D, H]
        (r".*mamba/conv_w(_x)?$",    P(None, None, tp)),   # [G, k, DI]
        (r".*mamba/conv_b(_x)?$",    P(None, tp)),         # [G, DI]
        (r".*mamba/conv_[wb]_bc$",   P()),                 # replicated (tiny)
        (r".*mamba/x_proj$",         P(None, tp, None)),   # [G, DI, R+2N]
        (r".*mamba/dt_proj$",        P(None, None, tp)),   # [G, R, DI]
        (r".*mamba/dt_bias$",        P(None, tp)),
        (r".*mamba/A_log$",          P(None, tp, None)),   # [G, DI, N]
        (r".*mamba/D$",              P(None, tp)),
        (r".*mamba/out_proj$",       P(None, tp, f)),      # [G, DI, D]
        (r".*mamba/norm/scale$",     P(None, tp)),
        # --- recurrent cells (LSTM/GRU): fused 4H/3H gate dim over TP ---
        (r".*rnn/cell/w_[xh]$",      P(None, f, tp)),      # [G, D|H, 4H/3H]
        (r".*rnn/cell/b(h_n)?$",     P(None, tp)),         # [G, 4H/3H]
        (r".*rnn/w_out$",            P(None, tp, f)),      # [G, H, D]
        # --- norms & leftovers: replicated ---
        (r".*",                      P()),
    ]


def _spec_for_path(path: str, shape, rules, mesh: Mesh) -> P:
    for pat, spec in rules:
        if re.search(pat, path):
            # pad spec to rank
            entries = list(spec) + [None] * (len(shape) - len(spec))
            return _guard(P(*entries[: len(shape)]), shape, mesh)
    return P()


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(getattr(p, "idx", p)))
    return "/".join(parts)


def param_specs(cfg: ModelConfig, params_tree: PyTree, mesh: Mesh,
                *, fsdp: bool = True) -> PyTree:
    """PartitionSpec pytree matching ``params_tree`` (works on shape structs)."""
    rules = _param_rules(cfg, mesh, fsdp=fsdp)

    def one(path, leaf):
        return _spec_for_path(_path_str(path), leaf.shape, rules, mesh)

    return jax.tree_util.tree_map_with_path(one, params_tree)


def param_shardings(cfg, params_tree, mesh, *, fsdp: bool = True) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(cfg, params_tree, mesh, fsdp=fsdp))


# ---------------------------------------------------------------------------
# activation / batch / cache specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, batch_shapes: PyTree) -> PyTree:  # noqa: ARG001 — uniform *_specs(cfg, shape-ish, mesh, tree) call shape
    """Input shardings for a shape cell.  Batch shards over all DP axes when
    divisible; long-context batch=1 cells leave batch unsharded and instead
    shard the *cache sequence* (flash-decode style) — see cache_specs.
    ``pure_dp`` plans additionally spread the batch over "model"."""
    dp = dp_axes(mesh)
    if cfg.pure_dp and "model" in mesh.axis_names:
        dp = dp + ("model",)

    def one(_path, leaf):
        spec = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1:
            spec[0] = dp
        return _guard(P(*spec), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, batch_shapes)


def cache_specs(cfg: ModelConfig, cache_tree: PyTree, mesh: Mesh, *, shard_seq: bool) -> PyTree:
    """Decode-cache shardings.  Layout: leaves are [G, B, S, heads, hd] (KV),
    [G, B, S, r] (MLA latent), or [G, B, ...] (SSM states).

    batch dim shards over DP when divisible.  For batch=1 long-context cells
    (``shard_seq=True``) the *sequence* dim of attention caches shards over
    ``data`` instead — the flash-decode partitioning; XLA SPMD turns softmax
    over the sharded axis into partial-reduction + combine.
    SSM states shard their channel dims over ``model``.
    """
    dp = dp_axes(mesh)
    tp = "model"

    def one(path, leaf):
        p = _path_str(path)
        shp = leaf.shape
        spec = [None] * len(shp)
        # leaves under "groups/" and "lead/" are stacked [G, B, ...];
        # "tail/" are [B, ...]
        off = 1 if p.startswith(("groups", "lead")) else 0

        def put(i, axis):
            if 0 <= off + i < len(spec):
                spec[off + i] = axis

        if "conv" in p:                     # [B, k-1, C]
            put(0, dp)
            put(2, tp)
        elif re.search(r"/[hc]$", p):       # mamba [B,DI,N] / rnn carry [B,H]
            put(0, dp)
            put(1, tp)
        elif re.search(r"(c_kv|k_rope)$", p):  # MLA latent [B,S,r]
            put(0, dp)
            if shard_seq:
                put(1, "data")
        elif re.search(r"/[kv]$", p):       # KV [B,S,KV,hd]
            put(0, dp)
            if shard_seq:
                put(1, "data")
            else:
                put(2, tp if cfg.attn_tp else None)
        return _guard(P(*spec), shp, mesh)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def opt_state_specs(cfg: ModelConfig, params_tree: PyTree, mesh: Mesh):
    """AdamW moments shard exactly like their parameters (ZeRO)."""
    pspec = param_specs(cfg, params_tree, mesh)
    return pspec


# ---------------------------------------------------------------------------
# activation sharding constraints (hillclimb: pin SPMD propagation)
# ---------------------------------------------------------------------------
#
# Unconstrained SPMD propagation can *lose* the batch sharding through long
# einsum/reshape chains (observed: attention recomputed per-device on the
# full global batch — 363× flops waste on smollm train_4k).  When a mesh is
# registered here, the model's group-scan body pins its activations to
# P(dp, ...) each iteration.  Thread-local so tests and single-device runs
# are untouched.

import contextlib
import threading

_ACT = threading.local()


@contextlib.contextmanager
def activation_constraints(mesh: Mesh, *, seq_axis: str | None = None,
                           batch_axes: tuple[str, ...] | None = None):
    """Enable batch-dim (and optionally sequence-dim) activation pinning."""
    prev = getattr(_ACT, "cfg", None)
    _ACT.cfg = (mesh, seq_axis, batch_axes)
    try:
        yield
    finally:
        _ACT.cfg = prev


def constrain_activation(x):
    """Pin [B, S, D] (or [B, ...]) activations to batch-over-DP sharding.
    No-op outside an ``activation_constraints`` context or when the batch
    doesn't divide (long_500k's batch=1)."""
    ctx = getattr(_ACT, "cfg", None)
    if ctx is None or not hasattr(x, "shape") or x.ndim < 2:
        return x
    mesh, seq_axis, batch_axes = ctx
    dp = batch_axes or dp_axes(mesh)
    spec = [dp] + [None] * (x.ndim - 1)
    if seq_axis and x.ndim >= 3:
        spec[1] = seq_axis
    guarded = _guard(P(*spec), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, guarded))


def activation_spec(mesh: Mesh, *dims) -> NamedSharding:
    return NamedSharding(mesh, P(*dims))
