"""mamba1-falcon7b: weights from the seed, the plain reference, and the work counts.

Nothing here imports the program.  ``init_weights`` makes the weights in the
layout the program's ``DecodeServer`` takes; the reference recomputes them
from the same seed.

Mamba-1 block, per layer (Gu & Dao 2023, eqs. 2a-2b with the selective
``Delta, B, C``; as the repository's ``mamba1`` block computes it):

    u      = rmsnorm(x) * ln
    xi, z  = u @ W_x, u @ W_z                              # [T, d_inner]
    xc     = silu(causal_conv_k(xi) + conv_b)              # depthwise, k taps
    dt, B, C = split(xc @ x_proj, [R, N, N])
    delta  = softplus(dt @ dt_proj + dt_bias)
    h_t    = exp(delta_t * A) * h_{t-1} + (delta_t * xc_t) B_t,  A = -exp(A_log)
    y_t    = h_t C_t + D * xc_t;   x = x + (y * silu(z)) @ out_proj
    logits = (rmsnorm(x) * final) @ head                   # untied head

Departure from the published model, in the program and here alike: Falcon
Mamba also RMS-normalises ``B``, ``C`` and ``dt`` inside the mixer
(``mixer_rms_eps``); the repository's block has no such norms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dims(s: dict):
    return (s["num_hidden_layers"], s["hidden_size"], s["intermediate_size"],
            s["state_size"], s["time_step_rank"], s["conv_kernel"],
            s["vocab_size"])


def init_weights(s: dict, key) -> dict:
    """Program-layout weights, f32, from ``key`` (traced under one jit)."""
    L, D, DI, N, R, K, V = _dims(s)
    k = jax.random.split(key, 10)
    nrm = lambda kk, shape, fan: jax.random.normal(kk, shape) / np.sqrt(fan)
    dt = jnp.exp(jax.random.uniform(k[0], (L, DI))
                 * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    return {
        "embed": {"table": jax.random.normal(k[1], (V, D)) * 0.02},
        "groups": {"b0_mamba1": {
            "ln": {"scale": jnp.ones((L, D), jnp.float32)},
            "mamba": {
                "w_x": nrm(k[2], (L, D, DI), D),
                "w_z": nrm(k[3], (L, D, DI), D),
                "conv_w": nrm(k[4], (L, K, DI), K),
                "conv_b": jnp.zeros((L, DI), jnp.float32),
                "x_proj": nrm(k[5], (L, DI, R + 2 * N), DI),
                "dt_proj": nrm(k[6], (L, R, DI), R),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, N + 1, dtype=jnp.float32), (L, DI, N))),
                "D": jnp.ones((L, DI), jnp.float32),
                "out_proj": nrm(k[7], (L, DI, D), DI),
            },
        }},
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
        "head": {"w": nrm(k[8], (D, V), D)},
    }


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def f32_dot(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def reference_hidden(s: dict, w: dict, tokens, mm=f32_dot):
    """Final-normed hidden states ``[B, T, D]``, layer by layer, in float32;
    every matrix product goes through ``mm`` (float32 at ``highest`` for the
    reference, the control's lower precision for it)."""
    L = s["num_hidden_layers"]
    eps = s["layer_norm_epsilon"]
    m = w["groups"]["b0_mamba1"]
    x = w["embed"]["table"][tokens]
    for l in range(L):
        p = {k: v[l] for k, v in m["mamba"].items()}
        x = _layer(x, m["ln"]["scale"][l], p, eps,
                   R=s["time_step_rank"], N=s["state_size"], mm=mm)
    return _rmsnorm(x, w["final_norm"]["scale"], eps)


@jax.jit
def _causal_conv(xi, cw, cb):
    K = cw.shape[0]
    T = xi.shape[1]
    pad = jnp.pad(xi, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(pad[:, i:i + T] * cw[i] for i in range(K)) + cb


def _silu(v):
    return v * jax.nn.sigmoid(v)


@functools.partial(jax.jit, static_argnames=("R", "N", "mm"))
def _layer(x, ln, p, eps, *, R, N, mm):
    u = _rmsnorm(x, ln, eps)
    xi, z = mm(u, p["w_x"]), mm(u, p["w_z"])
    xc = _silu(_causal_conv(xi, p["conv_w"], p["conv_b"]))
    dbc = mm(xc, p["x_proj"])
    dt, Bm, Cm = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    delta = jax.nn.softplus(mm(dt, p["dt_proj"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                    # [DI, N]

    def step(h, s_t):
        d_t, x_t, b_t, c_t = s_t
        h = jnp.exp(d_t[..., None] * A) * h + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    B = x.shape[0]
    h0 = jnp.zeros((B,) + A.shape, x.dtype)
    tm = lambda a: jnp.swapaxes(a, 0, 1)
    _, ys = jax.lax.scan(step, h0, (tm(delta), tm(xc), tm(Bm), tm(Cm)))
    y = (tm(ys) + xc * p["D"]) * _silu(z)
    return x + mm(y, p["out_proj"])


def reference_logits(s: dict, w: dict, hidden, mm=f32_dot):
    """Head over hidden rows ``[..., D]`` -> ``[..., V]``."""
    return mm(hidden, w["head"]["w"])


# ---------------------------------------------------------------------------
# work counts: follow the algorithm's shapes, whatever implements them
# ---------------------------------------------------------------------------

# Bytes per matrix element a product needs: on the TPU the served path's
# float32 dots at default precision round their operands to bfloat16, so a
# program that kept its matrices in bfloat16 would read 2 bytes of each.
OPERAND_BYTES = 2


def layer_matmul_params(s: dict) -> int:
    L, D, DI, N, R, K, V = _dims(s)
    return L * (2 * D * DI + DI * (R + 2 * N) + R * DI + DI * D)


def head_params(s: dict) -> int:
    return s["hidden_size"] * s["vocab_size"]


def vector_params(s: dict) -> int:
    """Norm scales, conv taps and biases, A_log, D: used elementwise in
    float32 (the embedding table, gathered by row, not included)."""
    L, D, DI, N, R, K, V = _dims(s)
    return L * (D + K * DI + DI + DI * N + DI + DI) + D


def weight_bytes(s: dict) -> int:
    """Float32 storage of every parameter, the embedding table included."""
    L, D, DI, N, R, K, V = _dims(s)
    return 4 * (layer_matmul_params(s) + head_params(s) + vector_params(s) + V * D)


def state_bytes_per_slot(s: dict) -> int:
    L, D, DI, N, R, K, V = _dims(s)
    return 4 * L * DI * (N + K - 1)


def prefill_flops(s: dict, prompt_len: int) -> float:
    """Required: every prompt token through every layer, the head once."""
    return 2.0 * (prompt_len * layer_matmul_params(s) + head_params(s))


def decode_flops_per_token(s: dict) -> float:
    return 2.0 * (layer_matmul_params(s) + head_params(s))


def decode_tick_bytes(s: dict, live_slots: int) -> float:
    """One tick: every matrix once at operand precision, the vectors in
    float32, the live slots' state read and written (the embedding table is
    gathered by row, not read whole)."""
    return (OPERAND_BYTES * (layer_matmul_params(s) + head_params(s))
            + 4 * vector_params(s) + 2.0 * live_slots * state_bytes_per_slot(s))
