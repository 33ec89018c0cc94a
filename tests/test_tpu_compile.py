"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at paper-lstm's width (D = H = 1024).

Nothing runs: the TPU compiler is asked to build each kernel for a chip
that is described, not attached, and refuses what the chip would refuse
(scoped-VMEM overflow, misaligned blocks).  Interpret mode cannot see
either.  The topology is described inside a module fixture, never at
import, and the tests skip where it cannot be described.
"""

import pytest

import jax
import jax.numpy as jnp

D = H = 1024


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compile cache off: an
    AOT compile for an absent chip is written to the cache but cannot be
    read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            try:
                yield topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one chip of the described host."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_stage(one_chip, cell, B, T, quant_bits=None):
    from repro import codegen

    run, graph = codegen.cell_stage_runner(
        cell, D, H, jit=False, interpret=False, quant_bits=quant_bits)
    consts = {n.name: _spec(one_chip, n.attr("shape")) for n in graph.consts()}
    x0 = {name: _spec(one_chip, (B, w)) for name, w in graph.states.items()}
    us = _spec(one_chip, (B, T, D))
    return jax.jit(run).lower(consts, x0, us).compile()


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("quant_bits", [None, 8], ids=["f32", "q8"])
@pytest.mark.parametrize("B,T", [(1, 37), (8, 64)], ids=["B1T37", "B8T64"])
def test_generated_lstm_stage_compiles_for_v5e(one_chip, quant_bits, B, T,
                                               precision):
    # the server runs the kernel at default precision; references and the
    # test suite run it at highest, whose f32 dots need more VMEM
    with jax.default_matmul_precision(precision):
        _assert_mosaic(_compile_stage(one_chip, "lstm", B, T, quant_bits))


@pytest.mark.parametrize("B,T", [(1, 37), (8, 64)], ids=["B1T37", "B8T64"])
def test_generated_gru_stage_compiles_for_v5e(one_chip, B, T):
    _assert_mosaic(_compile_stage(one_chip, "gru", B, T))


def test_lstm_cell_kernel_compiles_at_prime_length(one_chip):
    from repro.kernels.lstm_cell import ops

    B, T = 1, 37
    fn = jax.jit(lambda x, wx, wh, b, h0, c0: ops.lstm_seq(
        x, wx, wh, b, h0, c0, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (B, T, D)), _spec(one_chip, (D, 4 * H)),
        _spec(one_chip, (H, 4 * H)), _spec(one_chip, (4 * H,)),
        _spec(one_chip, (B, H)), _spec(one_chip, (B, H))).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("depth", [1, 5], ids=["layer", "stack"])
@pytest.mark.parametrize("rows", [384, 768])
def test_expert_gmm_compiles_at_deepseek_widths(one_chip, rows, depth):
    """The held-expert grouped product at DeepSeek-V2-Lite's widths (8
    experts of 2048 -> 1408 -> 2048): 64 decode slots x top-6 picks, and a
    128-token prefill; on a stack of one layer, and on a 5-layer stack read
    at a layer index.  The Mosaic call keeps its name, ``expert_gmm``."""
    from repro.kernels.expert_gmm import kernel


    def experts(x, w_in, w_out, sizes, layer):
        h = kernel.expert_gmm(x, w_in, sizes, layer, interpret=False)
        return kernel.expert_gmm(h, w_out, sizes, layer, interpret=False)

    compiled = jax.jit(experts).lower(
        _spec(one_chip, (rows, 2048)), _spec(one_chip, (depth, 8, 2048, 1408)),
        _spec(one_chip, (depth, 8, 1408, 2048)), _spec(one_chip, (9,), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert "%expert_gmm" in compiled.as_text()


def test_held_experts_compile_over_four_chips(topo, monkeypatch):
    """DeepSeek-V2-Lite's expert layer (8 held experts of a 5-layer stack,
    64 decode tokens) with the experts divided over a 4-chip ``model``
    axis, as the sharding rules lay them out: the Mosaic kernel runs inside
    the layer's shard_map, 2 experts a chip, and no chip gathers a whole
    expert stack.  (The CPU backend would pick the interpreter: the test
    asks for the Mosaic kernel.)"""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.deepseek_v2_lite_16b import CONFIG
    from repro.kernels.expert_gmm import ops
    from repro.models import moe

    monkeypatch.setattr(ops, "resolve_interpret", lambda _: False)
    cfg = dataclasses.replace(CONFIG, n_experts_held=8)
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4), ("pod", "data", "model"))
    p = jax.eval_shape(lambda: moe.moe_params(jax.random.PRNGKey(0), cfg))
    stack = {n: (5,) + p[n].shape for n in ("w_in", "w_gate", "w_out")}

    def spec(shape, *axes):
        return _spec(NamedSharding(mesh, P(*axes)), shape)

    args = {n: spec(shp, None, "model") for n, shp in stack.items()}
    args["router"] = spec(p["router"].shape)
    args["shared"] = {n: spec(a.shape) for n, a in p["shared"].items()}

    def layer(p, x, i):
        return moe.held_experts_apply(p, cfg, x, layer=i)

    with jax.set_mesh(mesh):
        compiled = jax.jit(layer).lower(
            args, spec((64, 1, cfg.d_model)),
            _spec(NamedSharding(mesh, P()), (), jnp.int32)).compile()
    hlo = compiled.as_text()
    _assert_mosaic(compiled)
    assert "%expert_gmm" in hlo
    gathered = re.findall(r"= (\S+) all-gather\(", hlo)
    assert not any(g.startswith("f32[5,8,") for g in gathered), gathered
