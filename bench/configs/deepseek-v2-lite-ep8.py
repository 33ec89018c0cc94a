"""deepseek-v2-lite-ep8: weights from the seed, the plain reference, and the work counts.

Nothing here imports the program.  ``init_weights`` makes the weights in the
layout the program's ``DecodeServer`` takes; the reference recomputes them
from the same seed.

DeepSeek-V2-Lite (arXiv:2405.04434; the hub's ``config.json``), one chip's
share under 8-way expert parallelism: the embedding, the dense layer 0 and
MoE layers 1-5, each holding routed experts 0-7 of 64, then the final norm
and the untied head.  Per layer, from the published equations:

    h      = rmsnorm(x) * ln_attn
    q      = h @ W_q  -> [H, dn + dr];  q_nope, q_pe
    c      = rmsnorm(h @ W_dkv) * kv_norm                  # latent, r wide
    k_pe   = h @ W_kr                                       # one rope key, dr
    k_nope = c @ W_uk -> [H, dn];  v = c @ W_uv -> [H, dv]
    q_pe, k_pe = yarn_rope(q_pe, t), yarn_rope(k_pe, t)
    a      = softmax_causal((q_nope . k_nope + q_pe . k_pe) * s) v
             s = (dn + dr)^-1/2 * yarn_get_mscale(40, 0.707)^2
    x      = x + a @ W_o
    h      = rmsnorm(x) * ln_mlp
    layer 0:    x = x + (silu(h @ W_g) * (h @ W_i)) @ W_o2          # 10944 wide
    layers 1-5: p = softmax(h @ W_router)      # all 64 experts, float32
                top-6 of p, weights not renormalised, routed scale 1
                x = x + sum_{picks e < 8} p_e expert_e(h) + shared(h)
    logits = (rmsnorm(x) * final) @ head

Experts 8-63 live on the other seven chips: what they would add is left
out here and in the program alike (the chip's share of the layer).

Departures, in the program and here alike:

* YaRN rope rotates interleaved pairs ``(x[2i], x[2i+1])``; the published
  code rotates the halves after permuting the channels to evens-then-odds.
  The permutation is the same for queries and keys, so every score is the
  same; with random weights the two are the same model.
* The YaRN cos/sin factor is ``mscale / mscale_all_dim`` = 1 at the
  published settings; it is computed, not assumed.
* Weights are random from the seed, not the published checkpoint.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(s: dict):
    return (s["num_hidden_layers"], s["first_k_dense_replace"], s["hidden_size"],
            s["num_attention_heads"], s["kv_lora_rank"], s["qk_nope_head_dim"],
            s["qk_rope_head_dim"], s["v_head_dim"], s["vocab_size"])


def _moe_dims(s: dict):
    return (s["router_experts"], s["n_routed_experts"], s["num_experts_per_tok"],
            s["moe_intermediate_size"], s["n_shared_experts"] * s["moe_intermediate_size"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _attn_weights(k, n, s):
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    kk = jax.random.split(k, 6)
    nrm = lambda key, shape, fan: jax.random.normal(key, shape) / np.sqrt(fan)
    return {
        "wq": nrm(kk[0], (n, D, H * (dn + dr)), D),
        "w_dkv": nrm(kk[1], (n, D, r), D),
        "w_krope": nrm(kk[2], (n, D, dr), D),
        "w_uk": nrm(kk[3], (n, r, H * dn), r),
        "w_uv": nrm(kk[4], (n, r, H * dv), r),
        "wo": nrm(kk[5], (n, H * dv, D), H * dv),
        "kv_norm": {"scale": jnp.ones((n, r), jnp.float32)},
    }


def _mlp_weights(k, lead: tuple, D, F):
    kk = jax.random.split(k, 3)
    nrm = lambda key, shape, fan: jax.random.normal(key, shape) / np.sqrt(fan)
    return {"w_in": nrm(kk[0], lead + (D, F), D), "w_gate": nrm(kk[1], lead + (D, F), D),
            "w_out": nrm(kk[2], lead + (F, D), F)}


def init_weights(s: dict, key) -> dict:
    """Program-layout weights, f32, from ``key`` (traced under one jit)."""
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    E, Eh, K, Fe, Fs = _moe_dims(s)
    G = L - L0
    k = jax.random.split(key, 8)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    moe = _mlp_weights(k[5], (G, Eh), D, Fe)
    moe["router"] = jax.random.normal(k[6], (G, D, E)) / np.sqrt(D)
    moe["shared"] = _mlp_weights(k[7], (G,), D, Fs)
    return {
        "embed": {"table": jax.random.normal(k[0], (V, D)) * 0.02},
        "lead": {"b0_attn": {
            "ln_attn": {"scale": ones(L0, D)}, "ln_mlp": {"scale": ones(L0, D)},
            "attn": _attn_weights(k[1], L0, s),
            "mlp": _mlp_weights(k[2], (L0,), D, s["intermediate_size"]),
        }},
        "groups": {"b0_moe": {
            "ln_attn": {"scale": ones(G, D)}, "ln_mlp": {"scale": ones(G, D)},
            "attn": _attn_weights(k[3], G, s),
            "moe": moe,
        }},
        "final_norm": {"scale": ones(D)},
        "head": {"w": jax.random.normal(k[4], (D, V)) / np.sqrt(D)},
    }


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

Q_BLOCK = 512          # queries per attention block (3 x 4352 tokens fit)


def f32_dot(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def _silu(v):
    return v * jax.nn.sigmoid(v)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(s: dict) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` frequencies."""
    rs, dim, base = s["rope_scaling"], s["qk_rope_head_dim"], s["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(find_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def softmax_scale(s: dict) -> float:
    rs = s["rope_scaling"]
    m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    return (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, inv_freq, cs):
    """x [B, T, ..., dr], interleaved pairs rotated by pos * inv_freq."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]          # [T, dr/2]
    shape = (1, ang.shape[0]) + (1,) * (x.ndim - 3) + (ang.shape[1],)
    cos, sin = (jnp.cos(ang) * cs).reshape(shape), (jnp.sin(ang) * cs).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("H", "dn", "dr", "dv", "eps", "mm"))
def _mla(h, p, inv_freq, cs, scale, *, H, dn, dr, dv, eps, mm):
    B, T, _ = h.shape
    q = mm(h, p["wq"]).reshape(B, T, H, dn + dr)
    c = _rmsnorm(mm(h, p["w_dkv"]), p["kv_norm"]["scale"], eps)
    pos = jnp.arange(T)
    q_pe = _rope(q[..., dn:], pos, inv_freq, cs)
    k_pe = _rope(mm(h, p["w_krope"]), pos, inv_freq, cs)                   # [B, T, dr]
    k_nope = mm(c, p["w_uk"]).reshape(B, T, H, dn)
    v = mm(c, p["w_uv"]).reshape(B, T, H, dv)
    # keys and queries of all heads as [B, H, T, dn + dr]
    k_all = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, T, H, dr))], -1)
    q_all = jnp.concatenate([q[..., :dn], q_pe], -1)
    kT = jnp.transpose(k_all, (0, 2, 3, 1))                                 # [B, H, d, T]
    vh = jnp.transpose(v, (0, 2, 1, 3))                                     # [B, H, T, dv]
    outs = []
    for a in range(0, T, Q_BLOCK):
        qb = jnp.transpose(q_all[:, a:a + Q_BLOCK], (0, 2, 1, 3))           # [B, H, q, d]
        sc = mm(qb, kT) * scale
        causal = jnp.arange(T)[None, :] <= (a + jnp.arange(qb.shape[2]))[:, None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.transpose(mm(pr, vh), (0, 2, 1, 3)))               # [B, q, H, dv]
    o = jnp.concatenate(outs, axis=1).reshape(B, T, H * dv)
    return mm(o, p["wo"])


def _mlp(h, p, mm):
    return mm(_silu(mm(h, p["w_gate"])) * mm(h, p["w_in"]), p["w_out"])


@functools.partial(jax.jit, static_argnames=("K", "mm"))
def _moe(h, p, *, K, mm):
    """Route over every expert; add what the held experts give for the
    picks routed to them, unweighted by renormalisation; shared experts."""
    probs = jax.nn.softmax(mm(h, p["router"]), axis=-1)                     # [B, T, E]
    top_p, top_e = jax.lax.top_k(probs, K)
    y = _mlp(h, p["shared"], mm)
    for e in range(p["w_in"].shape[0]):
        w = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)             # 0 if not picked
        pe = {n: p[n][e] for n in ("w_in", "w_gate", "w_out")}
        y = y + w[..., None] * _mlp(h, pe, mm)
    return y


@functools.partial(jax.jit, static_argnames=("mm",))
def _dense(h, p, *, mm):
    return _mlp(h, p, mm)


def reference_hidden(s: dict, w: dict, tokens, mm=f32_dot):
    """Final-normed hidden states ``[B, T, D]``, layer by layer, in float32;
    every matrix product goes through ``mm`` (float32 at ``highest`` for the
    reference, the control's lower precision for it)."""
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    eps = s["rms_norm_eps"]
    rs = s["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(s), jnp.float32)
    cs = yarn_get_mscale(rs["factor"], rs["mscale"]) / yarn_get_mscale(
        rs["factor"], rs["mscale_all_dim"])
    dims = dict(H=H, dn=dn, dr=dr, dv=dv, eps=eps, mm=mm)
    x = w["embed"]["table"][tokens]
    for stack, n in ((w["lead"]["b0_attn"], L0), (w["groups"]["b0_moe"], L - L0)):
        for l in range(n):
            p = jax.tree.map(lambda a, l=l: a[l], stack)
            x = x + _mla(_rmsnorm(x, p["ln_attn"]["scale"], eps), p["attn"], inv_freq, cs,
                         softmax_scale(s), **dims)
            h = _rmsnorm(x, p["ln_mlp"]["scale"], eps)
            x = x + (_dense(h, p["mlp"], mm=mm) if "mlp" in p
                     else _moe(h, p["moe"], K=s["num_experts_per_tok"], mm=mm))
    return _rmsnorm(x, w["final_norm"]["scale"], eps)


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


def attn_params(s: dict) -> int:
    """One MLA layer's matrices."""
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    return D * H * (dn + dr) + D * r + D * dr + r * H * dn + r * H * dv + H * dv * D


def expert_params(s: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def expected_held_picks(s: dict) -> float:
    """Picks per token that land on a held expert: top-k x held / routed."""
    E, Eh, K, Fe, Fs = _moe_dims(s)
    return K * Eh / E


def moe_layers(s: dict) -> int:
    return s["num_hidden_layers"] - s["first_k_dense_replace"]


def token_matmul_params(s: dict) -> float:
    """Matrix parameters one token meets in the layers: every MLA, the dense
    MLP, and per MoE layer the router, the shared experts and the expected
    held picks' experts."""
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    E, Eh, K, Fe, Fs = _moe_dims(s)
    moe = D * E + 3 * D * Fs + expected_held_picks(s) * expert_params(s)
    return L * attn_params(s) + L0 * 3 * D * s["intermediate_size"] + moe_layers(s) * moe


def head_params(s: dict) -> int:
    return s["hidden_size"] * s["vocab_size"]


def param_count(s: dict) -> int:
    """Every parameter of ``init_weights``, counted from the shapes."""
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    E, Eh, K, Fe, Fs = _moe_dims(s)
    norms = 2 * D + r
    lead = attn_params(s) + 3 * D * s["intermediate_size"] + norms
    moe = attn_params(s) + D * E + Eh * expert_params(s) + 3 * D * Fs + norms
    return L0 * lead + (L - L0) * moe + 2 * V * D + D


def weight_bytes(s: dict) -> int:
    """Float32 storage of every parameter, the embedding table included."""
    return 4 * param_count(s)


def cache_bytes_per_slot(s: dict, max_seq: int) -> int:
    """Float32 latent cache of one slot: every layer's c_kv and rope key."""
    return 4 * s["num_hidden_layers"] * max_seq * (s["kv_lora_rank"] + s["qk_rope_head_dim"])


def prefill_flops(s: dict, prompt_len: int) -> float:
    """Required: every prompt token through every layer's matrices, causal
    attention over the prompt (scores and values), the head once."""
    L, L0, D, H, r, dn, dr, dv, V = _dims(s)
    pairs = prompt_len * (prompt_len + 1) / 2
    attn = 2.0 * L * H * (dn + dr + dv) * pairs
    return 2.0 * (prompt_len * token_matmul_params(s) + head_params(s)) + attn


def decode_flops_per_token(s: dict) -> float:
    """Matrix FLOPs of one token (the expected held picks, 6 x 8/64, in the
    expert layers); attention over the cache grows with position and is
    not counted."""
    return 2.0 * (token_matmul_params(s) + head_params(s))


def expert_kernel_calls(s: dict) -> int:
    """Held-expert grouped products per token step: one per MoE layer."""
    return moe_layers(s)


def expert_kernel_cost(s: dict, tokens: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one MoE layer's held-expert product for ``tokens``
    routed tokens: the expected held picks through in, gate and out
    projections; the held experts' weights once at operand precision, and
    each pick's float32 rows (input read twice, hidden written twice and
    read once, output written)."""
    E, Eh, K, Fe, Fs = _moe_dims(s)
    D = s["hidden_size"]
    picks = tokens * expected_held_picks(s)
    flops = 2.0 * picks * expert_params(s)
    nbytes = OPERAND_BYTES * Eh * expert_params(s) + 4.0 * picks * 3 * (D + Fe)
    return flops, nbytes
