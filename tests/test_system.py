"""End-to-end behaviour of the system: the full paper workflow (§III)
executed programmatically, plus optimizer/sharding plumbing sanity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import ARCH_IDS, get_smoke_config
from repro.core.synthesis import NetworkSpec, create_top_module, synthesize
from repro.core.quantization import (
    default_format,
    fixed_mlp_forward,
    float_mlp_forward,
    output_snr_db,
)
from repro.models import lm
from repro.parallel import sharding as shd


def test_full_workflow_stages(rng):
    """Stage 1 state-space formation → 2 software simulation →
    3 fixed-point analysis → 4/5 synthesis → 6 optimization knob."""
    # 1-2: spec -> network -> simulate
    spec = NetworkSpec(num_inputs=3, num_hidden_layers=4, nodes_per_layer=4, num_outputs=2)
    params, forward = create_top_module(spec)
    u = jnp.asarray(rng.uniform(-1, 1, size=3), jnp.float32)
    y = forward(params, u)
    assert y.shape == (2,)

    # 3: fixed-point analysis picks a word length meeting a 40 dB target
    W = np.asarray(params["W"], np.float64)
    b = np.asarray(params["b"], np.float64)
    beta = np.asarray(params["beta"], np.float64)
    C = np.asarray(params["C"], np.float64)
    U = rng.uniform(-1, 1, size=(64, 3))
    y_ref = float_mlp_forward(W, b, beta, C, U)
    chosen = None
    for bits in (12, 16, 20, 24, 28):
        snr = float(np.mean(output_snr_db(
            y_ref, fixed_mlp_forward(W, b, beta, C, U, default_format(bits)))))
        if snr >= 40.0:
            chosen = bits
            break
    assert chosen is not None and chosen <= 24  # paper: 20-24 bits suffice

    # 4-5: implementation/synthesis report ("RTL" + utilization + timing)
    rep = synthesize(spec, batch=8)
    assert rep.hlo_bytes > 0 and rep.compile_s >= 0

    # 6: optimization — unroll (j) reduces the serial depth estimate
    rep_j = synthesize(dataclasses.replace(spec, unroll=4), batch=8)
    assert rep_j.serial_depth < rep.serial_depth


def test_optimizer_matches_reference_adamw(key):
    """Our AdamW == the textbook update on a toy problem."""
    cfg = optim.AdamWConfig(lr_peak=1e-2, warmup_steps=0, total_steps=10,
                            weight_decay=0.1, clip_norm=0.0)
    params = {"w": jnp.asarray([1.0, -2.0])}
    state = optim.init(params)
    g = {"w": jnp.asarray([0.5, 0.5])}
    new_params, new_state, m = optim.apply(cfg, g, state, params)

    lr = float(optim.lr_schedule(cfg, jnp.int32(1)))
    mhat = (0.1 * 0.5) / (1 - 0.9)
    vhat = (0.05 * 0.25) / (1 - 0.95)
    expect = np.asarray([1.0, -2.0]) - lr * (mhat / (np.sqrt(vhat) + 1e-8)
                                             + 0.1 * np.asarray([1.0, -2.0]))
    np.testing.assert_allclose(new_params["w"], expect, rtol=1e-5)


def test_grad_accumulation_equals_full_batch(key):
    """Microbatched (C-slow-in-time) grads == full-batch grads."""
    cfg = dataclasses.replace(get_smoke_config("smollm-135m"), remat=False)
    params = lm.init_params(cfg, key)
    toks = jax.random.randint(key, (8, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    loss_fn = lambda p, b: lm.train_loss(p, cfg, b)

    l1, g1, _ = optim.accumulate_grads(loss_fn, params, batch, 1)
    l4, g4, _ = optim.accumulate_grads(loss_fn, params, batch, 4)
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3), g1, g4
    )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_sharding_specs_cover_all_params(arch):
    """Every parameter gets a spec; remat flag never changes the loss."""
    from jax.sharding import Mesh

    cfg = get_smoke_config(arch)
    params = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.key(0))
    mesh = Mesh(np.array(jax.devices()).reshape(1, 1, 1), ("pod", "data", "model"))
    specs = shd.param_specs(cfg, params, mesh)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat_p) == len(flat_s)

    p_real = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 8
    if cfg.family == "encoder":
        batch = {"embeds": jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.frontend_dim)),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)}
    else:
        t = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
        batch = {"tokens": t, "labels": t}
    if cfg.family == "vlm":
        batch["memory"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.frontend_tokens, cfg.frontend_dim))
    l_remat, _ = lm.train_loss(p_real, dataclasses.replace(cfg, remat=True), batch)
    l_plain, _ = lm.train_loss(p_real, dataclasses.replace(cfg, remat=False), batch)
    np.testing.assert_allclose(float(l_remat), float(l_plain), rtol=1e-5)


def test_compile_cache_dir_env_wins_else_repo_path(monkeypatch):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None   # JAX reads the env
        monkeypatch.delenv(compile_cache.ENV)
        assert compile_cache.enable() == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent == compile_cache.REPO_ROOT
        assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
