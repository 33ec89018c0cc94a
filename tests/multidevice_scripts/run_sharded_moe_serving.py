"""Subprocess: MoE serving over a (data=2, model=4) mesh of 8 forced host
devices, deepseek-v2-lite-16b smoke config (8 experts, top-2).

MOE_EP_OK     — the held experts' weights stay divided over ``model`` (each
                device holds 2 of the 8), the decode program gathers no
                expert stack whole, and the sharded server's greedy tokens
                equal the unsharded server's, with both decode drivers: the
                expert layer runs as a shard_map over the experts, each
                device's part summed over ``model``.
MOE_EP_CUT_OK — a server that holds 4 of the 8 experts, divided 1 a device,
                gives the tokens of the unsharded server holding the same 4.

In both, every MLA latent stack (the leading dense layer's too) stays
divided over ``data`` by slot, and the decode program runs the
``mla_decode`` kernel inside a shard_map and gathers no latent stack whole.
"""
import dataclasses
import os
import re

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.runtime import DecodeServer, Request, ShardPlan  # noqa: E402

assert jax.device_count() == 8
plan = ShardPlan(make_local_mesh(dp=2, tp=4))


def reqs(cfg, n=4, max_new=5):
    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    prompt=list(rng.integers(1, cfg.vocab,
                                             size=int(rng.integers(3, 8)))),
                    max_new_tokens=max_new)
            for i in range(n)]


def drain(cfg, params, plan=None, **kw):
    srv = DecodeServer(cfg, params, num_slots=4, max_seq=24, plan=plan, **kw)
    for r in reqs(cfg):
        srv.submit(r)
    done = srv.run_until_drained()
    return {r.uid: list(r.out_tokens) for r in done}, srv


def pallas_names_in_shard_map(jaxpr, inside=False):
    """Names of the Pallas calls in ``jaxpr``, and whether each sits inside
    a shard_map."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], inside))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += pallas_names_in_shard_map(
                sub, inside or eqn.primitive.name == "shard_map")
    return found


def check(cfg, tag):
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    base, _ = drain(cfg, params)
    shard, srv = drain(cfg, params, plan=plan)
    w_in = srv.params["groups"]["b0_moe"]["moe"]["w_in"]     # [G, Eh, D, F]
    held = {s.data.shape[1] for s in w_in.addressable_shards}
    assert held == {cfg.experts_held // plan.tp}, held
    # GSPMD left alone with the kernel would all-gather every expert stack
    with jax.set_mesh(plan.mesh):
        hlo = jax.jit(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos)).lower(
            srv.params, jnp.zeros((4, 1), jnp.int32), srv.caches,
            jnp.zeros((4,), jnp.int32)).compile().as_text()
    whole = "f32[%s]" % ",".join(map(str, w_in.shape))
    gathered = re.findall(r"= (\S+) all-gather\(", hlo)
    assert not any(g.startswith(whole) for g in gathered), gathered
    for stack in (srv.caches["lead"]["b0_attn"], srv.caches["groups"]["b0_moe"]):
        for leaf in stack.values():
            assert {s.data.shape[1] for s in leaf.addressable_shards} == {4 // plan.dp}
            latent = "f32[%s]" % ",".join(map(str, leaf.shape))
            assert not any(g.startswith(latent) for g in gathered), gathered
    with jax.set_mesh(plan.mesh):
        jaxpr = jax.make_jaxpr(lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos))(
            srv.params, jnp.zeros((4, 1), jnp.int32), srv.caches,
            jnp.zeros((4,), jnp.int32))
    kernels = pallas_names_in_shard_map(jaxpr.jaxpr)
    assert ("mla_decode", True) in kernels and ("mla_decode", False) not in kernels, kernels
    assert base == shard, f"{tag}: per-token driver diverged:\n{base}\n{shard}"
    shard_p, _ = drain(cfg, params, plan=plan, persistent=True, block_k=3)
    assert base == shard_p, f"{tag}: persistent driver diverged:\n{base}\n{shard_p}"
    print(tag)


full = get_smoke_config("deepseek-v2-lite-16b")
check(full, "MOE_EP_OK")
check(dataclasses.replace(full, n_experts_held=4), "MOE_EP_CUT_OK")
