"""deepseek-v2-lite-ep8 and mamba-prefill-heavy at smoke size on the CPU: the
configuration module's counts against a direct parameter count, its
reference against the program's own prefill and decode path, and the
harness end to end on both new cells."""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.faults import FAULTS
from conftest import smoke_cell

SEED = 2**31 + 17
DSV2 = "dsv2lite-decode-heavy"


def dsv2_smoke_cell(grid=(8, 16), outputs=(8, 16), slots=4):
    """The cell as BENCHMARK.json defines it, cut to a three-layer model of
    width 64 (1 dense + 2 MoE layers, 4 of 8 experts held, top-2) whose
    original context of 32 puts the smoke positions on YaRN's ramp."""
    from bench import harness

    cell = harness.load_cell(DSV2)
    cfg = copy.deepcopy(cell.config)
    cfg["sizes"].update(
        num_hidden_layers=3, hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
        moe_intermediate_size=48, n_routed_experts=4, router_experts=8,
        num_experts_per_tok=2, n_shared_experts=1, vocab_size=256)
    cfg["sizes"]["rope_scaling"] = dict(cfg["sizes"]["rope_scaling"], factor=4,
                                        original_max_position_embeddings=32)
    cfg["program"]["overrides"].update(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=96, d_ff_expert=48,
        n_experts=8, n_experts_held=4, top_k=2, n_shared_experts=1, vocab=256,
        rope_yarn_factor=4.0, rope_yarn_orig_max_pos=32)
    mix = copy.deepcopy(cell.mix)
    mix["prompt"].update(min=grid[0], max=grid[-1], median=grid[0], grid=list(grid))
    mix["output"].update(min=outputs[0], max=outputs[1], median=outputs[0])
    mix.update(slots=slots, clients=slots, warm_seconds=0.5)
    data = copy.deepcopy(cell.data)
    data["limits"].update(min_compared_tokens=4, sample_requests=16)
    cell.config, cell.mix, cell.data = cfg, mix, data
    return cell


def test_counts_against_the_program():
    from bench import harness
    from repro.models import lm

    cell = harness.load_cell(DSV2)
    m, s = cell.model, cell.config["sizes"]
    cfg = harness.model_config(cell)
    shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert m.param_count(s) == n
    assert n == pytest.approx(1.002e9, rel=1e-3)                    # 4.01 GB in f32
    assert m.weight_bytes(s) == 4 * n
    # by layer: MLA 13.8 M, shared experts 17.3 M, held experts 69.2 M,
    # the dense layer's MLP 67.2 M, embedding and head 210 M each
    assert m.attn_params(s) == 2048 * 16 * 192 + 2048 * 512 + 2048 * 64 \
        + 512 * 16 * 128 * 2 + 16 * 128 * 2048
    assert m.attn_params(s) == pytest.approx(13.8e6, rel=5e-3)
    assert 8 * m.expert_params(s) == pytest.approx(69.2e6, rel=5e-3)
    assert m.head_params(s) == 2048 * 102400
    assert m.expected_held_picks(s) == 6 * 8 / 64
    # a token's matrices: 6 MLA, the dense MLP, and per MoE layer the router,
    # the shared experts and 0.75 expected held picks
    per_moe = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    want = 6 * m.attn_params(s) + 3 * 2048 * 10944 + 5 * per_moe
    assert m.decode_flops_per_token(s) == pytest.approx(2 * (want + 2048 * 102400))
    assert m.prefill_flops(s, 1) - 2 * (want + 2048 * 102400) == 2 * 6 * 16 * 320
    assert m.cache_bytes_per_slot(s, 8192) * 64 == pytest.approx(7.25e9, rel=1e-3)
    flops, nbytes = m.expert_kernel_cost(s, 64)
    assert flops == 2 * 48 * 3 * 2048 * 1408
    assert nbytes == 2 * 8 * 3 * 2048 * 1408 + 4 * 48 * 3 * (2048 + 1408)
    assert m.expert_kernel_calls(s) == 5


def test_reference_matches_prefill_then_decode():
    """Prefill 11 tokens, decode 5 through the cache; the logits match the
    reference's full forward.  Both are float32 on the CPU (the reference at
    highest), so the gap is rounding in a different order of operations:
    2e-4 of the largest logit, as for the other configurations."""
    from bench import harness
    from repro.models import lm

    cell = dsv2_smoke_cell()
    cfg = harness.model_config(cell)
    sizes = cell.config["sizes"]
    w = jax.jit(functools.partial(cell.model.init_weights, sizes))(harness.seed_key(5))
    harness.check_layout(cfg, w)
    rng = np.random.default_rng(0)
    P, N = 11, 6
    toks = rng.integers(0, sizes["vocab_size"], (2, P + N)).astype(np.int32)
    logits, caches = lm.prefill(w, cfg, jnp.asarray(toks[:, :P]))
    got = [logits]
    caches = jax.tree.map(lambda a: jnp.pad(a, [(0, 0)] * 2 + [(0, N)] + [(0, 0)] * (a.ndim - 3))
                          if a.ndim >= 3 else a, caches)
    for t in range(P, P + N - 1):
        logits, caches = lm.decode_step(w, cfg, jnp.asarray(toks[:, t:t + 1]), caches,
                                        jnp.full((2,), t, jnp.int32))
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], axis=1)

    hid = cell.model.reference_hidden(sizes, w, jnp.asarray(toks))
    ref = np.asarray(cell.model.reference_logits(sizes, w, hid[:, P - 1:P + N - 1]))
    np.testing.assert_allclose(got, ref, atol=2e-4 * np.max(np.abs(ref)), rtol=0)


def test_weights_depend_on_seed_only():
    from bench import harness

    cell = dsv2_smoke_cell()
    init = jax.jit(functools.partial(cell.model.init_weights, cell.config["sizes"]))
    a, b, c = (init(harness.seed_key(s)) for s in (2**31 + 9, 2**31 + 9, 3))
    assert all(jax.tree.leaves(jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    assert not all(jax.tree.leaves(jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, c)))


def _run(cell):
    from bench import harness

    return harness.run_cell(cell, SEED, 1.0, False, require_tpu=False, log=lambda *a, **k: None)


@pytest.mark.parametrize("workload", [DSV2, "mamba-prefill-heavy"])
def test_sound_run_is_correct(workload):
    from bench import harness

    out = _run(dsv2_smoke_cell() if workload == DSV2 else smoke_cell(workload))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in harness.load_cell(workload).metrics}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(dsv2_smoke_cell())
    assert not out["correct"], out["checks"]
