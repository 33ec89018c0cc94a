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
def one_chip():
    """A sharding on one chip of a described v5e:2x2 host, with the
    persistent compile cache off: an AOT compile for an absent chip is
    written to the cache but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


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
