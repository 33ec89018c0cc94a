"""Recurrent block for the layer stack: LN → LSTM/GRU cell → out-proj, residual.

Mirrors ``models.ssm``'s prefill/decode/init_state contract so
``models.transformer.apply_block`` treats a recurrent block exactly like a
Mamba block: prefill runs the whole sequence and emits the final ``(h, c)``
carry as the decode state; decode applies the one-step transition map.  The
carry is the entire serving state — O(1) per slot, the cheapest cache in the
framework (``ModelConfig.kv_cache_bytes`` accounts it as 2·H·4 bytes).

Fast paths: ``cfg.use_pallas`` routes LSTM prefill through the hand-written
fused Pallas ``lstm_cell`` kernel (one [4H, D+H] contraction per step,
VMEM-resident carry); ``cfg.use_codegen`` routes prefill through the
*generated* fused kernel from :mod:`repro.codegen` instead — same VMEM-carry
structure, but produced from the cell's datapath IR, so it covers GRU (and
any registered cell) too.  The jnp path runs the same math through
``cells.run_cell`` / ``lax.scan`` and is the oracle for both.
"""

from __future__ import annotations

from typing import Any, TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

from . import cells

if TYPE_CHECKING:
    from repro.models.config import ModelConfig

PyTree = Any


def recurrent_params(key, cfg: "ModelConfig") -> PyTree:
    D, H = cfg.d_model, cfg.rnn_hidden_actual
    k1, k2 = jax.random.split(key)
    ctor = cells.lstm_params if cfg.rnn_cell == "lstm" else cells.gru_params
    return {
        "cell": ctor(k1, D, H, cfg.p_dtype),
        "w_out": dense_init(k2, (H, D), cfg.p_dtype),
    }


def recurrent_init_state(cfg: "ModelConfig", batch: int) -> PyTree:
    H = cfg.rnn_hidden_actual
    st = {"h": jnp.zeros((batch, H), jnp.float32)}
    if cfg.rnn_cell == "lstm":
        st["c"] = jnp.zeros((batch, H), jnp.float32)
    return st


def _carry_in(cfg: "ModelConfig", state: PyTree):
    return (state["h"], state["c"]) if cfg.rnn_cell == "lstm" else state["h"]


def _carry_out(cfg: "ModelConfig", carry) -> PyTree:
    if cfg.rnn_cell == "lstm":
        return {"h": carry[0], "c": carry[1]}
    return {"h": carry}


# Generated-kernel runners, one per (cell, D, H) datapath shape.  The runner
# closes over graph structure only — weights are re-bound every call, so
# trained parameters flow through without recompiling the generator.
_CODEGEN_RUNNERS: dict[tuple, Any] = {}


def _codegen_seq(cell: str, p_cell: PyTree, u: jnp.ndarray, carry0,
                 quant_bits: int = 0):
    """Prefill via the codegen Pallas backend (works for lstm AND gru).
    ``quant_bits`` in (0, 8] routes the gate contraction through the int8
    MACC datapath of the generated kernel (paper's fixed-point stage)."""
    from repro import codegen

    B, _, D = u.shape
    H = cells.cell_hidden_size(p_cell, cell)
    key = (cell, D, H, quant_bits)
    run = _CODEGEN_RUNNERS.get(key)
    if run is None:
        run, _ = codegen.cell_stage_runner(
            cell, D, H, quant_bits=quant_bits or None)
        _CODEGEN_RUNNERS[key] = run
    if carry0 is None:
        carry0 = cells.init_carry(cell, p_cell, (B,))
    x0 = {"h": carry0[0], "c": carry0[1]} if cell == "lstm" else {"h": carry0}
    if jax.sharding.get_abstract_mesh().size > 1:
        # GSPMD cannot partition a Mosaic kernel: under a device mesh (a
        # sharded DecodeServer traces its prefill inside ``jax.set_mesh``)
        # every device runs the whole stage on replicated operands
        from jax.sharding import PartitionSpec as P

        run = jax.shard_map(run, in_specs=P(), out_specs=P(), check_vma=False)
    finals, ys = run(codegen.bind_cell_params(cell, p_cell), x0,
                     u.astype(jnp.float32))
    carry = (finals["h"], finals["c"]) if cell == "lstm" else finals["h"]
    return ys, carry


def recurrent_prefill(p: PyTree, cfg: "ModelConfig", u: jnp.ndarray,
                      state: PyTree | None = None):
    """u: [B, T, D] → (y [B, T, D], state).  Resumes from ``state`` if given."""
    carry0 = None if state is None else _carry_in(cfg, state)
    if cfg.use_codegen and cfg.rnn_cell in ("lstm", "gru"):
        y, carry = _codegen_seq(cfg.rnn_cell, p["cell"], u, carry0,
                                quant_bits=cfg.quant_gate_bits)
    elif cfg.use_pallas and cfg.rnn_cell == "lstm":
        from repro.kernels.lstm_cell import ops as lstm_ops

        c = p["cell"]
        h0c0 = (None, None) if carry0 is None else carry0
        y, h_f, c_f = lstm_ops.lstm_seq(
            u.astype(jnp.float32), c["w_x"].astype(jnp.float32),
            c["w_h"].astype(jnp.float32), c["b"].astype(jnp.float32),
            h0=h0c0[0], c0=h0c0[1],
        )
        carry = (h_f, c_f)
    else:
        y, carry = cells.cell_seq(cfg.rnn_cell, p["cell"], u, carry0,
                                  unroll=cfg.scan_unroll)
    out = y.astype(u.dtype) @ p["w_out"]
    return out, _carry_out(cfg, carry)


def recurrent_decode(p: PyTree, cfg: "ModelConfig", u_t: jnp.ndarray, state: PyTree):
    """One token: u_t [B, 1, D] → (y [B, 1, D], state') — the transition map f."""
    carry = _carry_in(cfg, state)
    if cfg.rnn_cell == "lstm":
        h_new, c_new = cells.lstm_step(p["cell"], carry, u_t[:, 0])
        carry = (h_new, c_new)
    else:
        h_new = cells.gru_step(p["cell"], carry, u_t[:, 0])
        carry = h_new
    y = (h_new.astype(u_t.dtype) @ p["w_out"])[:, None]
    return y, _carry_out(cfg, carry)
