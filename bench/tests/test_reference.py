"""Each configuration's plain reference against the program's own prefill
and decode path, at smoke size on the CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke_cell


@pytest.mark.parametrize("workload", ["lstm-prefill-heavy", "mamba-decode-heavy"])
def test_reference_matches_prefill_then_decode(workload):
    from bench import harness
    from repro.models import lm

    cell = smoke_cell(workload)
    cfg = harness.model_config(cell)
    sizes = cell.config["sizes"]
    w = jax.jit(functools.partial(cell.model.init_weights, sizes))(harness.seed_key(5))
    harness.check_layout(cfg, w)
    rng = np.random.default_rng(0)
    P, N = 11, 6
    toks = rng.integers(0, sizes["vocab_size"], (2, P + N)).astype(np.int32)

    logits, caches = lm.prefill(w, cfg, jnp.asarray(toks[:, :P]))
    got = [logits]
    for t in range(P, P + N - 1):
        logits, caches = lm.decode_step(w, cfg, jnp.asarray(toks[:, t:t + 1]), caches,
                                        jnp.full((2,), t, jnp.int32))
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], axis=1)        # [2, N, V]

    hid = cell.model.reference_hidden(sizes, w, jnp.asarray(toks))
    ref = np.asarray(cell.model.reference_logits(sizes, w, hid[:, P - 1:P + N - 1]))
    scale = np.max(np.abs(ref))
    np.testing.assert_allclose(got, ref, atol=2e-4 * scale, rtol=0)


@pytest.mark.parametrize("workload", ["lstm-prefill-heavy", "mamba-decode-heavy"])
def test_weights_depend_on_seed_only(workload):
    from bench import harness

    cell = smoke_cell(workload)
    init = jax.jit(functools.partial(cell.model.init_weights, cell.config["sizes"]))
    a, b, c = (init(harness.seed_key(s)) for s in (2**31 + 9, 2**31 + 9, 3))
    same = jax.tree.leaves(jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b))
    assert all(same)
    diff = jax.tree.leaves(jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, c))
    assert not all(diff)
