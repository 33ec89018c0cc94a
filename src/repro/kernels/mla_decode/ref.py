"""jnp oracle of MLA's absorbed attention over the latent cache: every row
of the whole ``S_max`` stack, masked past each query's position.  It is also
the model's path for a block of several query tokens (chunked prefill)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30  # large-but-finite: keeps softmax NaN-free on fully-masked rows


def mla_decode_ref(q_lat, q_rope, c_kv, k_rope, layer, qpos, *, scale: float):
    """``q_lat`` [B, S, H, r], ``q_rope`` [B, S, H, dr], latent stacks
    ``c_kv`` [G, B, T, r] and ``k_rope`` [G, B, T, dr] read at ``layer``,
    ``qpos`` [B, S] absolute query positions -> ``o_lat`` [B, S, H, r]:
    softmax over rows ``<= qpos`` of ``scale · (q_lat·c_kv + q_rope·k_rope)``,
    applied to ``c_kv``."""
    c_kv, k_rope = c_kv[layer], k_rope[layer]
    s_lat = jnp.einsum("bshr,btr->bhst", q_lat, c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    T = c_kv.shape[1]
    mask = jnp.arange(T)[None, None, None, :] <= qpos[:, None, :, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,btr->bshr", probs, c_kv.astype(jnp.float32))
