"""Recurrent-cell subsystem: LSTM/GRU as state-space systems.

Oracles are pure-numpy step loops (no jax in the reference path); the cells
must match through every execution style — run_scan, C-slow vectorized
streams, the fused Pallas kernel (interpret mode), and the serving stack.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cslow import cslow_vectorized
from repro.core.state_space import mlp_forward, resolve_activation, run_scan
from repro.core.synthesis import NetworkSpec, synthesize
from repro.recurrent import cells as rnn_cells

RNG = np.random.default_rng(11)


def _sig(x):
    return 1.0 / (1.0 + np.exp(-x))


def _np_lstm(params, us, h, c):
    """Pure-numpy step loop: the run_scan oracle."""
    w_x, w_h, b = (np.asarray(params[k], np.float64) for k in ("w_x", "w_h", "b"))
    H = w_h.shape[0]
    ys = []
    for u in np.asarray(us, np.float64):
        z = u @ w_x + h @ w_h + b
        i_g, f_g = _sig(z[..., :H]), _sig(z[..., H:2 * H])
        g_g, o_g = np.tanh(z[..., 2 * H:3 * H]), _sig(z[..., 3 * H:])
        c = f_g * c + i_g * g_g
        h = o_g * np.tanh(c)
        ys.append(h)
    return h, c, np.stack(ys)


def _np_gru(params, us, h):
    w_x, w_h, b, bh_n = (np.asarray(params[k], np.float64)
                         for k in ("w_x", "w_h", "b", "bh_n"))
    H = w_h.shape[0]
    ys = []
    for u in np.asarray(us, np.float64):
        zx = u @ w_x + b
        zh = h @ w_h
        r = _sig(zx[..., :H] + zh[..., :H])
        z = _sig(zx[..., H:2 * H] + zh[..., H:2 * H])
        n = np.tanh(zx[..., 2 * H:] + r * (zh[..., 2 * H:] + bh_n))
        h = (1.0 - z) * n + z * h
        ys.append(h)
    return h, np.stack(ys)


def _rand_lstm(key, d, h):
    p = rnn_cells.lstm_params(key, d, h)
    # perturb biases so the forget-gate +1 init doesn't hide sign errors
    return jax.tree.map(lambda x: x + 0.1 * jax.random.normal(key, x.shape), p)


# ---------------------------------------------------------------------------
# run_scan vs numpy oracle (the property the paper's eq. 1 form must keep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,D,H,seed", [(8, 4, 6, 0), (16, 8, 8, 1), (5, 3, 12, 2)])
def test_lstm_run_scan_matches_numpy(T, D, H, seed):
    key = jax.random.PRNGKey(seed)
    params = _rand_lstm(key, D, H)
    us = jax.random.normal(jax.random.PRNGKey(seed + 100), (T, D))
    (h_f, c_f), ys = rnn_cells.run_cell("lstm", params, us)
    h_np, c_np, ys_np = _np_lstm(params, us, np.zeros(H), np.zeros(H))
    np.testing.assert_allclose(h_f, h_np, atol=1e-5)
    np.testing.assert_allclose(c_f, c_np, atol=1e-5)
    np.testing.assert_allclose(ys, ys_np, atol=1e-5)
    # Mealy output: y[k] = h[k+1]; final carry h == last emitted output
    np.testing.assert_allclose(ys[-1], h_f, atol=1e-6)


@pytest.mark.parametrize("T,D,H,seed", [(8, 4, 6, 0), (12, 6, 10, 3)])
def test_gru_run_scan_matches_numpy(T, D, H, seed):
    key = jax.random.PRNGKey(seed)
    params = rnn_cells.gru_params(key, D, H)
    params = jax.tree.map(lambda x: x + 0.1 * jax.random.normal(key, x.shape), params)
    us = jax.random.normal(jax.random.PRNGKey(seed + 7), (T, D))
    h_f, ys = rnn_cells.run_cell("gru", params, us)
    h_np, ys_np = _np_gru(params, us, np.zeros(H))
    np.testing.assert_allclose(h_f, h_np, atol=1e-5)
    np.testing.assert_allclose(ys, ys_np, atol=1e-5)


@pytest.mark.parametrize("unroll", [2, 4])
def test_lstm_unroll_invariance(unroll):
    """The paper's j knob is semantics-free on recurrent cells too."""
    key = jax.random.PRNGKey(5)
    params = _rand_lstm(key, 6, 8)
    us = jax.random.normal(key, (16, 6))
    (h1, c1), y1 = rnn_cells.run_cell("lstm", params, us, unroll=1)
    (hj, cj), yj = rnn_cells.run_cell("lstm", params, us, unroll=unroll)
    np.testing.assert_allclose(h1, hj, atol=1e-6)
    np.testing.assert_allclose(y1, yj, atol=1e-6)


@pytest.mark.parametrize("cell,C", [("lstm", 3), ("gru", 4)])
def test_cslow_vectorized_tuple_carries(cell, C):
    """C-slow streams through one datapath == independent runs — with the
    LSTM's (h, c) *tuple* carry riding the stream axis on every leaf."""
    key = jax.random.PRNGKey(9)
    ctor = _rand_lstm if cell == "lstm" else rnn_cells.gru_params
    params = ctor(key, 5, 7)
    model = rnn_cells.make_cell(cell, params)
    x0s = rnn_cells.init_carry(cell, params, (C,))
    uss = jax.random.normal(key, (C, 10, 5))
    carry_c, ys_c = cslow_vectorized(model, None, x0s, uss)
    for c in range(C):
        carry_1, ys_1 = run_scan(model, None,
                                 rnn_cells.init_carry(cell, params), uss[c])
        np.testing.assert_allclose(ys_c[c], ys_1, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a[c], b, atol=1e-6),
            carry_c, carry_1,
        )


# ---------------------------------------------------------------------------
# fused Pallas kernel (interpret mode) vs ref
# ---------------------------------------------------------------------------

def _kernel_case(Bsz, T, D, H, dtype=jnp.float32, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(Bsz, T, D)), dtype)
    w_x = jnp.asarray(r.normal(size=(D, 4 * H)) / np.sqrt(D), jnp.float32)
    w_h = jnp.asarray(r.normal(size=(H, 4 * H)) / np.sqrt(H), jnp.float32)
    b = jnp.asarray(r.normal(size=(4 * H,)) * 0.2, jnp.float32)
    h0 = jnp.asarray(r.normal(size=(Bsz, H)), jnp.float32)
    c0 = jnp.asarray(r.normal(size=(Bsz, H)), jnp.float32)
    return x, w_x, w_h, b, h0, c0


@pytest.mark.parametrize("Bsz,T,D,H", [(1, 16, 8, 8), (2, 32, 16, 24),
                                       (3, 48, 12, 16), (4, 64, 32, 32),
                                       (1, 37, 8, 8), (9, 37, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_kernel_matches_ref(Bsz, T, D, H, dtype):
    from repro.kernels.lstm_cell.ops import lstm_seq, lstm_seq_ref

    x, w_x, w_h, b, h0, c0 = _kernel_case(Bsz, T, D, H, dtype)
    y_k, h_k, c_k = lstm_seq(x, w_x, w_h, b, h0, c0)
    y_r, h_r, c_r = lstm_seq_ref(x, w_x, w_h, b, h0, c0)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2  # acceptance: 1e-5 fp32
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(h_k, h_r, atol=tol, rtol=tol)
    np.testing.assert_allclose(c_k, c_r, atol=tol, rtol=tol)


@pytest.mark.parametrize("chunk,block_b", [(8, 1), (16, 2), (64, 4)])
def test_lstm_kernel_blocking_invariance(chunk, block_b):
    """Tile choices must not change the math (carry crosses chunks exactly)."""
    from repro.kernels.lstm_cell.ops import lstm_seq, lstm_seq_ref

    x, w_x, w_h, b, h0, c0 = _kernel_case(4, 32, 8, 16, seed=3)
    y_r, h_r, _ = lstm_seq_ref(x, w_x, w_h, b, h0, c0)
    y_k, h_k, _ = lstm_seq(x, w_x, w_h, b, h0, c0, chunk=chunk, block_b=block_b)
    np.testing.assert_allclose(y_k, y_r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h_k, h_r, atol=1e-5, rtol=1e-5)


def test_lstm_kernel_carry_resume():
    """Running [0:T] == running [0:T/2] then resuming from (h, c) — the
    prefill-continuation contract the decode server relies on."""
    from repro.kernels.lstm_cell.ops import lstm_seq

    x, w_x, w_h, b, h0, c0 = _kernel_case(2, 32, 8, 8, seed=4)
    y_full, h_full, c_full = lstm_seq(x, w_x, w_h, b, h0, c0)
    y_a, h_a, c_a = lstm_seq(x[:, :16], w_x, w_h, b, h0, c0)
    y_b, h_b, c_b = lstm_seq(x[:, 16:], w_x, w_h, b, h_a, c_a)
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b], 1), y_full, atol=1e-5)
    np.testing.assert_allclose(h_b, h_full, atol=1e-5)
    np.testing.assert_allclose(c_b, c_full, atol=1e-5)


def test_lstm_kernel_lut_path():
    """Quantized gates (ROM-LUT idiom): kernel == LUT oracle exactly-ish, and
    within LUT resolution of the exact-activation result."""
    from repro.kernels.lstm_cell.ops import lstm_seq, lstm_seq_lut_ref, lstm_seq_ref
    from repro.kernels.tanh_lut.ref import make_lut

    x, w_x, w_h, b, h0, c0 = _kernel_case(2, 24, 8, 12, seed=5)
    lut = make_lut(12)
    y_k, h_k, c_k = lstm_seq(x, w_x, w_h, b, h0, c0, lut)
    y_r, h_r, c_r = lstm_seq_lut_ref(x, w_x, w_h, b, h0, c0, lut)
    np.testing.assert_allclose(y_k, y_r, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(c_k, c_r, atol=2e-6, rtol=1e-5)
    y_exact, _, _ = lstm_seq_ref(x, w_x, w_h, b, h0, c0)
    assert float(jnp.max(jnp.abs(y_k - y_exact))) < 2e-3  # 12-bit table


# ---------------------------------------------------------------------------
# model block + serving
# ---------------------------------------------------------------------------

def _smoke(cell="lstm"):
    from repro.configs import get_smoke_config

    cfg = get_smoke_config("paper-lstm")
    return cfg if cell == "lstm" else dataclasses.replace(cfg, rnn_cell="gru")


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_block_prefill_resume_and_decode(cell, key):
    """Block-level state handoff: prefill(T) == prefill(T/2) → resumed
    decode steps; the (h, c) carry IS the whole cache."""
    from repro.recurrent import block as rnn_block

    cfg = _smoke(cell)
    p = rnn_block.recurrent_params(key, cfg)
    u = jax.random.normal(key, (2, 8, cfg.d_model))
    y_full, st_full = rnn_block.recurrent_prefill(p, cfg, u)
    y_half, st = rnn_block.recurrent_prefill(p, cfg, u[:, :4])
    ys = [y_half]
    for t in range(4, 8):
        y_t, st = rnn_block.recurrent_decode(p, cfg, u[:, t:t + 1], st)
        ys.append(y_t)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_full, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 st, st_full)


def test_lstm_pallas_block_matches_jnp(key):
    from repro.recurrent import block as rnn_block

    cfg = _smoke()
    p = rnn_block.recurrent_params(key, cfg)
    u = jax.random.normal(key, (2, 8, cfg.d_model))
    y_jnp, st_jnp = rnn_block.recurrent_prefill(p, cfg, u)
    cfg_pl = dataclasses.replace(cfg, use_pallas=True)
    y_pl, st_pl = rnn_block.recurrent_prefill(p, cfg_pl, u)
    np.testing.assert_allclose(y_pl, y_jnp, atol=1e-5, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 st_pl, st_jnp)


def test_lstm_decode_server_end_to_end(key):
    """Acceptance: an LSTM ModelConfig decodes through DecodeServer under
    continuous batching, and matches the single-request oracle."""
    from repro.models import lm
    from repro.runtime.server import DecodeServer, Request, splice_cache

    cfg = _smoke()
    params = lm.init_params(cfg, key)
    srv = DecodeServer(cfg, params, num_slots=2, max_seq=32)
    for i in range(4):
        srv.submit(Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=4))
    done = srv.run_until_drained()
    assert len(done) == 4 and all(len(r.out_tokens) == 4 for r in done)

    prompt = [2, 2, 3]
    lg, pc = lm.prefill(params, cfg, jnp.asarray([prompt]))
    c = splice_cache(lm.init_cache(cfg, 1, 32), pc, 0)
    cur = int(jnp.argmax(lg[0]))
    outs = [cur]
    for t in range(3):
        lg, c = lm.decode_step(params, cfg, jnp.asarray([[cur]]), c, jnp.int32(3 + t))
        cur = int(jnp.argmax(lg[0]))
        outs.append(cur)
    assert [r for r in done if r.uid == 1][0].out_tokens == outs


def test_recurrent_cache_accounting():
    cfg = _smoke()
    H = cfg.rnn_hidden_actual
    assert cfg.kv_cache_bytes(batch=3, seq=999) == cfg.n_layers * 3 * 2 * H * 4
    assert _smoke("gru").kv_cache_bytes(batch=3, seq=999) == cfg.n_layers * 3 * H * 4


# ---------------------------------------------------------------------------
# synthesize() + activation table (satellite regressions)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_synthesize_recurrent_spec(cell):
    spec = NetworkSpec(num_inputs=3, num_hidden_layers=2, nodes_per_layer=8,
                       num_outputs=2, cell=cell, seq_len=16)
    rep = synthesize(spec, batch=4)
    assert rep.hlo_bytes > 0 and rep.output_shape == (4, 2)
    assert rep.serial_depth == 16
    rep_j = synthesize(dataclasses.replace(spec, unroll=4), batch=4)
    assert rep_j.serial_depth < rep.serial_depth  # the j knob still works


def test_synthesize_recurrent_requires_seq_len():
    with pytest.raises(ValueError, match="seq_len"):
        synthesize(NetworkSpec(3, 2, 8, 2, cell="lstm"))


@pytest.mark.parametrize("act", ["sigmoid", "gelu", "identity", "relu", "tanh"])
def test_mlp_forward_every_advertised_activation(act, key):
    """Regression: getattr(jnp, name) crashed for sigmoid/gelu/identity."""
    W = jax.random.normal(key, (3, 4, 4)) * 0.5
    b = jnp.zeros((3, 4))
    beta = jax.random.normal(key, (4, 2))
    C = jax.random.normal(key, (2, 4))
    u = jnp.asarray([0.3, -0.4])
    y = mlp_forward(W, b, beta, C, u, activation_name=act)
    assert y.shape == (2,) and bool(jnp.all(jnp.isfinite(y)))
    x = beta @ u
    f = resolve_activation(act)
    for i in range(3):
        x = f(W[i] @ x + b[i])
    np.testing.assert_allclose(y, C @ x, atol=1e-6)


def test_resolve_activation_unknown_name_errors():
    with pytest.raises(ValueError, match="unknown activation"):
        resolve_activation("swish2")
