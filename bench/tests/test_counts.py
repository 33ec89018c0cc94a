"""FLOP and byte counts against shapes worked by hand, and against the
program's own parameter count at full size (abstract shapes only)."""

import jax
import pytest

from bench import harness


def full(workload):
    return harness.load_cell(workload)


def program_params(cell):
    from repro.models import lm

    cfg = harness.model_config(cell)
    shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(shapes))


def test_paper_lstm_counts():
    cell = full("lstm-prefill-heavy")
    m, s = cell.model, cell.config["sizes"]
    assert program_params(cell) == 108_307_456
    assert m.weight_bytes(s) == 4 * 108_307_456                 # 433.2 MB
    # per layer: Wx 1024x4096 + Wh 1024x4096 + Wout 1024x1024
    assert m.layer_matmul_params(s) == 8 * (2 * 1024 * 4096 + 1024 * 1024)
    assert m.head_params(s) == 1024 * 32000
    assert m.prefill_flops(s, 1) == pytest.approx(216.6e6, rel=1e-3)
    assert m.prefill_flops(s, 1) - 2 * m.layer_matmul_params(s) == pytest.approx(65.5e6, rel=1e-3)
    assert m.decode_flops_per_token(s) == pytest.approx(216.6e6, rel=1e-3)
    assert m.state_bytes_per_slot(s) == 8 * 2 * 1024 * 4
    # matrices at bfloat16 operand precision, vectors (8 x (ln 1024 + bias
    # 4096) + final 1024) in float32, plus 64 slots' (h, c) read and written
    vectors = 8 * (1024 + 4096) + 1024
    assert m.decode_tick_bytes(s, 64) == 2 * (108_307_456 - vectors) + 4 * vectors \
        + 2 * 64 * 65536
    flops, nbytes = m.stage_kernel_cost(s, 384)
    assert flops == 2 * 384 * 2048 * 4096
    assert nbytes == 2 * 2048 * 4096 + 4 * (4096 + 384 * 2048 + 4 * 1024)
    assert m.stage_kernel_calls(s) == 8


def test_falcon_mamba_counts():
    cell = full("mamba-decode-heavy")
    m, s = cell.model, cell.config["sizes"]
    assert m.weight_bytes(s) == 4 * program_params(cell)       # ~5.5 GB
    assert m.weight_bytes(s) == pytest.approx(5.50e9, rel=5e-3)
    per_layer = 2 * 4096 * 8192 + 8192 * (256 + 32) + 256 * 8192 + 8192 * 4096
    assert m.layer_matmul_params(s) == 8 * per_layer
    assert m.decode_flops_per_token(s) == pytest.approx(2.22e9, rel=5e-3)
    # in float32 storage, one tick at 64 slots is 5.07 GB: the weights less
    # the embedding table, plus the state [8 layers, 8192, 16 + 3] f32 of 64
    # slots read and written
    assert m.state_bytes_per_slot(s) == 8 * 8192 * 19 * 4
    state = 2 * 64 * m.state_bytes_per_slot(s)
    assert m.weight_bytes(s) - 4 * 65024 * 4096 + state == pytest.approx(5.07e9, rel=2e-3)
    # at bfloat16 operand precision the matrices are half of that
    assert m.decode_tick_bytes(s, 64) == \
        2 * (m.layer_matmul_params(s) + m.head_params(s)) + 4 * m.vector_params(s) + state
    assert m.decode_tick_bytes(s, 64) == pytest.approx(2.86e9, rel=5e-3)


def test_peaks_table_is_keyed_by_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
