"""Unified model configuration covering all ten assigned architectures.

One declarative dataclass; every family (dense / moe / ssm / hybrid /
encoder-audio / vlm) is expressed by flags consumed by
``repro.models.transformer``.  The dry-run, training step, serving step, and
sharding rules all key off this config — it is the "GUI form" of the paper's
code generator, grown up.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

BlockKind = Literal[
    "attn",          # self-attention + FFN (dense transformer block)
    "attn_local",    # sliding-window self-attention + FFN
    "moe",           # self-attention + MoE FFN
    "cross",         # cross-attention (to vision/audio memory) + FFN
    "mamba1",        # Mamba-1 selective-scan block
    "mamba2",        # Mamba-2 / SSD block
    "shared_attn",   # Zamba-style shared transformer block (weights reused)
    "recurrent",     # LSTM/GRU cell block (paper's intrinsic state-space NN)
]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "encoder", "vlm", "recurrent"]
    n_layers: int
    d_model: int
    vocab: int
    # --- attention ---
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_global: float = 0.0   # gemma3: global layers use a larger base
    # YaRN context extension (deepseek-v2): factor 0 = plain RoPE
    rope_yarn_factor: float = 0.0
    rope_yarn_orig_max_pos: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    partial_rotary: float = 1.0      # fraction of head_dim carrying RoPE
    sliding_window: int = 0          # >0 enables local attention windows
    global_every: int = 0            # gemma3: 1 global layer per N (pattern)
    causal: bool = True              # False for encoder-only (hubert)
    attn_logit_softcap: float = 0.0
    # --- FFN ---
    d_ff: int = 0
    mlp_act: Literal["silu", "gelu", "tanh"] = "silu"
    gated_mlp: bool = True
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    norm_topk_prob: bool = True      # renormalise the top-k router weights
    # experts 0..n_experts_held-1 of every MoE layer live here (0 = all): the
    # chip's share under expert parallelism; the router still spans n_experts
    n_experts_held: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_group_size: int = 0          # dispatch group tokens (0 = 2048 default);
                                     # dispatch einsum work ∝ group size (§Perf)
    # --- MLA (deepseek) ---
    use_mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- SSM (mamba) ---
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2
    ssm_chunk: int = 0               # 0 = per-impl default (the j knob)
    mamba_headdim: int = 64          # mamba2 only
    dt_rank: int = 0                 # mamba1; 0 = ceil(d_model/16)
    # --- recurrent (LSTM/GRU) ---
    rnn_cell: Literal["lstm", "gru"] = "lstm"
    rnn_hidden: int = 0              # 0 = d_model
    # --- hybrid (zamba2) ---
    attn_block_period: int = 0       # shared attn applied once per N ssm blocks
    shared_attn_lora_rank: int = 0   # per-application LoRA on shared weights
    # --- vlm / audio frontends (stubs per task spec) ---
    cross_attn_every: int = 0        # llama-vision: cross block per N
    frontend_dim: int = 0            # precomputed patch/frame embedding dim
    frontend_tokens: int = 0         # number of vision/audio memory tokens
    # --- misc ---
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: str = "float32"           # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = True               # activation checkpointing in scan body
    scan_unroll: int = 1             # the paper's j knob
    use_pallas: bool = False         # TPU kernels (tests use interpret mode)
    use_codegen: bool = False        # codegen-generated fused cell kernels
    quant_gate_bits: int = 0         # <=8 and >0: int8 gate MACC in the
                                     # generated cell kernel (paper §IV-B)
    sequence_parallel: bool = False  # shard seq over model axis in non-attn regions
    # attention TP is only legal when heads divide the model axis; plans may
    # disable it per-arch (smollm 9H, phi4 24H vs model=16):
    attn_tp: bool = True
    # small-model plan: no TP at all — weights replicated over "model",
    # batch sharded over ALL axes (pod×data×model). Right regime for models
    # whose weights fit one chip (smollm); a §Perf hillclimb knob.
    pure_dp: bool = False
    # dense ("attn") layers BEFORE the scan (deepseek's first_k_dense_replace)
    first_dense_layers: int = 0
    # blocks appended AFTER the scan when n_layers % period != 0
    # (gemma3: 62 = 6*10 + 2 local; zamba2: 38 = 6*6 + 2 mamba2):
    tail_pattern: tuple = ()

    # ------------------------------------------------------------------
    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank_actual(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def n_mamba_heads(self) -> int:
        return self.d_inner // self.mamba_headdim

    @property
    def rnn_hidden_actual(self) -> int:
        return self.rnn_hidden or self.d_model

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def p_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def layer_pattern(self) -> tuple[str, ...]:
        """The repeating block pattern (the scan body's inner structure).

        Heterogeneous stacks (gemma3 local:global, llama-vision cross-attn,
        zamba2 ssm+shared-attn) become a uniform scan over *groups* of
        ``period`` blocks — the paper's resource sharing applied at group
        granularity.
        """
        if self.family == "ssm":
            return ("mamba1",)
        if self.family == "recurrent":
            return ("recurrent",)
        if self.family == "hybrid":
            return ("mamba2",) * self.attn_block_period + ("shared_attn",)
        if self.family == "moe":
            return ("moe",)
        if self.family == "vlm" and self.cross_attn_every:
            return ("attn",) * (self.cross_attn_every - 1) + ("cross",)
        if self.global_every:
            return ("attn_local",) * (self.global_every) + ("attn",)
        return ("attn",)

    @property
    def n_groups(self) -> int:
        period = len(self.layer_pattern)
        body = self.n_layers - len(self.tail_pattern) - self.first_dense_layers
        if body % period:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} minus tail "
                f"{len(self.tail_pattern)} and {self.first_dense_layers} leading "
                f"dense layers not divisible by pattern period "
                f"{period} ({self.layer_pattern})"
            )
        return body // period

    @property
    def is_decoder(self) -> bool:
        return self.family != "encoder"

    def kv_cache_bytes(self, batch: int, seq: int) -> int:
        """Serving-cache footprint (bf16), for capacity planning/reports."""
        bpe = 2
        pat = self.layer_pattern
        n_groups = self.n_groups
        if self.use_mla:
            attn = batch * seq * (self.kv_lora_rank + self.qk_rope_head_dim) * bpe
        else:
            attn = 2 * batch * seq * self.n_kv_heads * self.head_dim * bpe
        total = 0
        for kind in pat:
            if kind in ("attn", "moe", "cross"):
                total += attn
            elif kind == "attn_local":
                s = min(seq, self.sliding_window)
                total += 2 * batch * s * self.n_kv_heads * self.head_dim * bpe
            elif kind == "shared_attn":
                total += 2 * batch * seq * self.n_kv_heads * self.head_dim * bpe
            elif kind == "recurrent":
                # f32 (h, c) carry — O(1) in seq; the cheapest serving state
                n_regs = 2 if self.rnn_cell == "lstm" else 1
                total += batch * n_regs * self.rnn_hidden_actual * 4
            elif kind in ("mamba1", "mamba2"):
                if kind == "mamba1":
                    total += batch * self.d_inner * (self.ssm_state + self.d_conv - 1) * 4
                else:
                    total += batch * (
                        self.n_mamba_heads * self.mamba_headdim * self.ssm_state
                        + (self.d_inner + 2 * self.ssm_state) * (self.d_conv - 1)
                    ) * 4
        return total * n_groups + self.first_dense_layers * attn


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


TRAIN_4K = ShapeSpec("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524_288, 1, "decode")

ALL_SHAPES: tuple[ShapeSpec, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def applicable_shapes(cfg: ModelConfig) -> tuple[ShapeSpec, ...]:
    """Task rules: encoder-only ⇒ no decode; pure full attention ⇒ no 500k."""
    shapes: list[ShapeSpec] = [TRAIN_4K, PREFILL_32K]
    if cfg.is_decoder:
        shapes.append(DECODE_32K)
        sub_quadratic = (
            cfg.family in ("ssm", "hybrid", "recurrent")
            or (cfg.sliding_window > 0 and cfg.global_every > 0)  # mostly-local
        )
        if sub_quadratic:
            shapes.append(LONG_500K)
    return tuple(shapes)
