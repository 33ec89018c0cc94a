"""paper-lstm: weights from the seed, the plain reference, and the work counts.

Nothing here imports the program.  ``init_weights`` makes the weights in the
layout the program's ``DecodeServer`` takes (the harness checks that layout
against the program's own abstract init); the reference recomputes them from
the same seed and never sees the program's arrays.

Block, per layer ``l`` (the repository's recurrent block; GNMT's widths):

    u   = rmsnorm(x) * ln_l
    z_t = u_t @ Wx_l + h_{t-1} @ Wh_l + b_l           # gates i, f, g, o
    c_t = sig(f) * c_{t-1} + sig(i) * tanh(g);  h_t = sig(o) * tanh(c_t)
    x   = x + h @ Wout_l
    logits = (rmsnorm(x) * final) @ table.T           # tied embedding head
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dims(s: dict):
    return s["num_layers"], s["hidden_size"], s["rnn_hidden_size"], s["vocab_size"]


def init_weights(s: dict, key) -> dict:
    """Program-layout weights, f32, from ``key`` (traced under one jit)."""
    L, D, H, V = _dims(s)
    k = jax.random.split(key, 4)
    b = jnp.zeros((L, 4 * H), jnp.float32).at[:, H:2 * H].set(1.0)
    return {
        "embed": {"table": jax.random.normal(k[0], (V, D)) * 0.02},
        "groups": {"b0_recurrent": {
            "ln": {"scale": jnp.ones((L, D), jnp.float32)},
            "rnn": {
                "cell": {
                    "w_x": jax.random.normal(k[1], (L, D, 4 * H)) / jnp.sqrt(D),
                    "w_h": jax.random.normal(k[2], (L, H, 4 * H)) / jnp.sqrt(H),
                    "b": b,
                },
                "w_out": jax.random.normal(k[3], (L, H, D)) / jnp.sqrt(H),
            },
        }},
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
    }


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def f32_dot(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def reference_hidden(s: dict, w: dict, tokens, mm=f32_dot):
    """Final-normed hidden states ``[B, T, D]`` of the token rows, layer by
    layer, in float32; every matrix product goes through ``mm`` (float32 at
    ``highest`` for the reference, the control's lower precision for it)."""
    L = s["num_layers"]
    eps = s["norm_eps"]
    g = w["groups"]["b0_recurrent"]
    x = w["embed"]["table"][tokens]
    for l in range(L):
        x = _layer(x, g["ln"]["scale"][l], g["rnn"]["cell"]["w_x"][l],
                   g["rnn"]["cell"]["w_h"][l], g["rnn"]["cell"]["b"][l],
                   g["rnn"]["w_out"][l], eps, mm=mm)
    return _rmsnorm(x, w["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("mm",))
def _layer(x, ln, wx, wh, b, wout, eps, *, mm):
    H = wh.shape[0]
    zx = mm(_rmsnorm(x, ln, eps), wx) + b                # [B, T, 4H]

    def step(carry, z_t):
        h, c = carry
        z = z_t + mm(h, wh)
        i, f = jax.nn.sigmoid(z[:, :H]), jax.nn.sigmoid(z[:, H:2 * H])
        g, o = jnp.tanh(z[:, 2 * H:3 * H]), jax.nn.sigmoid(z[:, 3 * H:])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    B = x.shape[0]
    h0 = jnp.zeros((B, H), x.dtype)
    _, hs = jax.lax.scan(step, (h0, h0), jnp.swapaxes(zx, 0, 1))
    return x + mm(jnp.swapaxes(hs, 0, 1), wout)


def reference_logits(s: dict, w: dict, hidden, mm=f32_dot):
    """Head over hidden rows ``[..., D]`` -> ``[..., V]``."""
    return mm(hidden, w["embed"]["table"].T)


# ---------------------------------------------------------------------------
# work counts: follow the algorithm's shapes, whatever implements them
# ---------------------------------------------------------------------------

# Bytes per matrix element a product needs: on the TPU the served path's
# float32 dots at default precision round their operands to bfloat16, so a
# program that kept its matrices in bfloat16 would read 2 bytes of each.
OPERAND_BYTES = 2


def layer_matmul_params(s: dict) -> int:
    L, D, H, _ = _dims(s)
    return L * (D * 4 * H + H * 4 * H + H * D)


def head_params(s: dict) -> int:
    return s["hidden_size"] * s["vocab_size"]


def vector_params(s: dict) -> int:
    """Norm scales and biases, used elementwise in float32."""
    L, D, H, _ = _dims(s)
    return L * (D + 4 * H) + D


def weight_bytes(s: dict) -> int:
    """Float32 storage of every parameter (the tied table counted once)."""
    return 4 * (layer_matmul_params(s) + head_params(s) + vector_params(s))


def state_bytes_per_slot(s: dict) -> int:
    L, _, H, _ = _dims(s)
    return 4 * L * 2 * H


def prefill_flops(s: dict, prompt_len: int) -> float:
    """Required: every prompt token through every layer, the head once."""
    return 2.0 * (prompt_len * layer_matmul_params(s) + head_params(s))


def decode_flops_per_token(s: dict) -> float:
    return 2.0 * (layer_matmul_params(s) + head_params(s))


def decode_tick_bytes(s: dict, live_slots: int) -> float:
    """One tick: every matrix once at operand precision (the tied table is
    the head), the vectors in float32, the live slots' state read and
    written."""
    return (OPERAND_BYTES * (layer_matmul_params(s) + head_params(s))
            + 4 * vector_params(s) + 2.0 * live_slots * state_bytes_per_slot(s))


def stage_kernel_calls(s: dict) -> int:
    """Generated-stage calls per prefill: one per layer."""
    return s["num_layers"]


def stage_kernel_cost(s: dict, prompt_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one generated-stage call: one layer over the prompt
    at B=1 -- the gate ROM ``[D+H, 4H]`` once at operand precision, the
    bias, the inputs and outputs and the carried state in float32."""
    _, D, H, _ = _dims(s)
    flops = 2.0 * prompt_len * (D + H) * 4 * H
    nbytes = (OPERAND_BYTES * (D + H) * 4 * H
              + 4.0 * (4 * H + prompt_len * (D + H) + 2 * 2 * H))
    return flops, nbytes
