"""Top-level language / encoder / VLM model: embed → scan(groups) → head.

The whole network is one state-space system (paper eq. 8):
  * training/prefill: state = activations x[k] flowing across layer-groups k
    (layers-as-time; the scan is the paper's shared datapath),
  * decode: state = (KV caches / SSM states); one serve_step is one
    application of the state-update map f with the new token as input u[k].
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain_activation

from .config import ModelConfig
from .layers import dense_init, embed, embedding_params, rmsnorm, rmsnorm_params
from .transformer import (
    apply_block,
    group_params,
    init_cache,
    lead_params,
    shared_block_params,
    tail_params,
)

PyTree = Any

__all__ = [
    "init_params",
    "forward",
    "train_loss",
    "prefill",
    "prefill_chunk",
    "decode_step",
    "init_cache",
    "param_count",
]


def init_params(cfg: ModelConfig, key) -> PyTree:
    ks = jax.random.split(key, 5)
    params: dict[str, Any] = {}
    if cfg.family == "encoder":
        # audio frontend stub: precomputed frame embeddings -> linear proj
        params["embed"] = {"proj": dense_init(ks[0], (cfg.frontend_dim, cfg.d_model), cfg.p_dtype)}
    else:
        params["embed"] = embedding_params(ks[0], cfg.vocab, cfg.d_model, cfg.p_dtype)

    lead = lead_params(jax.random.fold_in(key, 5), cfg)
    if lead is not None:
        params["lead"] = lead

    gkeys = jax.random.split(ks[1], cfg.n_groups)
    params["groups"] = jax.vmap(lambda k: group_params(k, cfg))(gkeys)

    shared = shared_block_params(ks[2], cfg)
    if shared is not None:
        params["shared"] = shared

    tail = tail_params(ks[4], cfg)
    if tail is not None:
        params["tail"] = tail

    params["final_norm"] = rmsnorm_params(cfg.d_model, cfg.p_dtype)
    if not cfg.tie_embeddings:
        params["head"] = {"w": dense_init(ks[3], (cfg.d_model, cfg.vocab), cfg.p_dtype)}
    return params


def param_count(params: PyTree) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# group scan
# ---------------------------------------------------------------------------

def _scan_stack(params_stack, caches_stack, pattern, shared, cfg: ModelConfig, h, aux, *,
                memory, pos, mode):
    """Scan one group body (a period of ``pattern``) over stacked params and
    caches; returns (h, aux, stacked new caches).

    Caches go in as xs and come out as ys, except MLA latents in the serving
    modes: those ride the carry whole and each layer writes its rows in
    place, so a [G, B, S_max, r] stack is never held twice.  MLA alone takes
    this path because its latent stack is the one cache a served
    configuration holds at a size where the second copy does not fit
    (deepseek-v2-lite's 6 GB stack on a 16 GB chip); ``mla_decode`` reads
    and writes its layer of the stack by index.  KV caches and recurrent
    states stay xs/ys: their updates take one layer's cache, and the
    served recurrent states are a few MB.

    Likewise the held experts' weights stay whole in the serving modes and
    the expert kernel reads its layer of them, rather than a per-layer
    copy."""
    carried, experts = {}, {}
    if caches_stack is not None and cfg.use_mla and mode in ("decode", "chunk"):
        carried = {n: c for n, c in caches_stack.items()
                   if isinstance(c, dict) and "c_kv" in c}
        caches_stack = {n: c for n, c in caches_stack.items() if n not in carried}
    if mode != "train":
        names = ("w_in", "w_gate", "w_out")
        experts = {n: {w: p["moe"][w] for w in names}
                   for n, p in params_stack.items() if "moe" in p}
        params_stack = {n: dict(p, moe={w: v for w, v in p["moe"].items() if w not in names})
                        if n in experts else p for n, p in params_stack.items()}

    def group_body(carry, xs):
        h, aux, carried, layer = carry
        h = constrain_activation(h)  # pin batch-over-DP each group (no-op on 1 dev)
        p_grp, cache_grp = xs
        new_caches = {}
        carried = dict(carried)
        for i, kind in enumerate(pattern):
            name = f"b{i}_{kind}"
            if name in experts:
                p_grp = dict(p_grp, **{name: dict(p_grp[name], moe={
                    **p_grp[name]["moe"], **experts[name]})})
            if name in carried:
                h, carried[name], aux_i = apply_block(
                    p_grp[name], shared, cfg, kind, h,
                    memory=memory, cache=carried[name], pos=pos, mode=mode, layer=layer,
                )
                aux = aux + aux_i
                continue
            c_in = None if cache_grp is None else cache_grp.get(name)
            h, c_out, aux_i = apply_block(
                p_grp[name], shared, cfg, kind, h,
                memory=memory, cache=c_in, pos=pos, mode=mode,
                layer=layer if name in experts else None,
            )
            aux = aux + aux_i
            new_caches[name] = c_out if c_out is not None else jnp.zeros((), jnp.float32)
        return (h, aux, carried, layer + 1), new_caches

    body = group_body
    if cfg.remat and mode == "train":
        body = jax.checkpoint(group_body)

    (h, aux, carried, _), out_caches = jax.lax.scan(
        body, (h, aux, carried, jnp.zeros((), jnp.int32)), (params_stack, caches_stack),
        unroll=cfg.scan_unroll
    )
    return h, aux, {**out_caches, **carried}


def _apply_groups(params, cfg: ModelConfig, x, *, memory, caches, pos, mode):
    shared = params.get("shared")
    aux = jnp.zeros((), jnp.float32)
    out_caches = {}
    h = x
    if cfg.first_dense_layers:
        h, aux, out_caches["lead"] = _scan_stack(
            params["lead"], None if caches is None else caches["lead"], ("attn",),
            shared, cfg, h, aux, memory=memory, pos=pos, mode=mode)
    h, aux, out_caches["groups"] = _scan_stack(
        params["groups"], None if caches is None else caches["groups"], cfg.layer_pattern,
        shared, cfg, h, aux, memory=memory, pos=pos, mode=mode)

    if cfg.tail_pattern:
        tail_in = None if caches is None else caches.get("tail")
        tail_out = {}
        for i, kind in enumerate(cfg.tail_pattern):
            name = f"t{i}_{kind}"
            c_in = None if tail_in is None else tail_in.get(name)
            h, c_out, aux_i = apply_block(
                params["tail"][name], shared, cfg, kind, h,
                memory=memory, cache=c_in, pos=pos, mode=mode,
            )
            aux = aux + aux_i
            if c_out is not None:
                tail_out[name] = c_out
        if tail_out:
            out_caches["tail"] = tail_out
    return h, aux, out_caches


def _embed_in(params, cfg: ModelConfig, tokens_or_embeds):
    if cfg.family == "encoder":
        return tokens_or_embeds.astype(cfg.act_dtype) @ params["embed"]["proj"]
    return embed(params["embed"], tokens_or_embeds).astype(cfg.act_dtype)


def _head(params, cfg: ModelConfig, h):
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if cfg.tie_embeddings:
        return h @ params["embed"]["table"].T
    return h @ params["head"]["w"]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens, *, memory=None, mode="train"):
    """Full-sequence forward.  mode: "train" (no caches) | "prefill"."""
    x = _embed_in(params, cfg, tokens)
    caches = None
    h, aux, out_caches = _apply_groups(
        params, cfg, x, memory=memory, caches=caches, pos=None, mode=mode
    )
    logits = _head(params, cfg, h)
    if mode == "prefill":
        return logits, out_caches, aux
    return logits, aux


def train_loss(params, cfg: ModelConfig, batch, z_loss_coef: float = 1e-4):
    """batch: {"tokens": [B,S] or "embeds": [B,S,F], "labels": [B,S],
    optional "memory": [B,M,F]} → (loss, metrics)."""
    inputs = batch.get("embeds", batch.get("tokens"))
    logits, aux = forward(params, cfg, inputs, memory=batch.get("memory"), mode="train")
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum((lse - gold) * mask) / denom
    zl = jnp.sum(jnp.square(lse) * mask) / denom
    loss = ce + z_loss_coef * zl + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "z_loss": zl, "router_aux": aux}


def prefill(params, cfg: ModelConfig, tokens, *, memory=None):
    """Returns (last-token logits, caches) — cache seeding for serving."""
    logits, caches, _ = forward(params, cfg, tokens, memory=memory, mode="prefill")
    return logits[:, -1], caches


def prefill_chunk(params, cfg: ModelConfig, tokens, caches, pos, *, memory=None):
    """Resumable prefill: one chunk of the prompt scan.

    tokens [B, S_c] are applied against existing ``caches`` (the decode-layout
    state) starting at absolute position ``pos`` — exactly the state-space
    view of the paper: prefill is the same iteration x[k+1] = f(x[k], u[k])
    as decode, so it can stop and resume at any step boundary.  Chaining
    chunks from a fresh ``init_cache`` reproduces one-shot :func:`prefill`;
    stopping after any chunk yields a checkpointed mid-prompt state that a
    prefix cache can store and later splice into any slot.

    Returns (last-token logits [B, V], updated caches).
    """
    x = _embed_in(params, cfg, tokens)
    h, _, out_caches = _apply_groups(
        params, cfg, x, memory=memory, caches=caches, pos=pos, mode="chunk"
    )
    logits = _head(params, cfg, h)
    return logits[:, -1], out_caches


def decode_step(params, cfg: ModelConfig, tokens, caches, pos, *, memory=None):
    """One serving step: tokens [B,1] at position(s) ``pos`` (scalar or [B]).

    This is f(x[k], u[k]) of the serving state-space system: the caches are
    the state, the token is the input, the logits are g's output.
    """
    x = _embed_in(params, cfg, tokens)
    h, _, out_caches = _apply_groups(
        params, cfg, x, memory=memory, caches=caches, pos=pos, mode="decode"
    )
    logits = _head(params, cfg, h)
    return logits[:, -1], out_caches
