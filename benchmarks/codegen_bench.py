"""Codegen acceptance bench: generated kernel vs hand-written vs XLA.

Sweeps the registered cell types and two sizes each, timing the IR-compiled
XLA scan against the IR-generated fused Pallas kernel; for LSTM it also
times the hand-written ``kernels/lstm_cell`` path on identical shapes — the
parity oracle the generator must match within 10% on the paper-lstm config
(both run the same one-contraction-per-step / VMEM-carry structure, so the
ratio should be ~1).

NOTE: on CPU the Pallas paths run in interpret mode — orders of magnitude
slower than compiled jnp and only meaningful *relative to each other*
(generated vs hand-written).  The gen/hand ratio is the portable number.

Writes ``experiments/codegen_bench.csv`` and ``benchmarks/codegen_bench.json``
(the JSON is uploaded as a CI artifact).
"""

from __future__ import annotations

import csv
import json
import os

import jax
import jax.numpy as jnp

from repro.codegen import (bind_cell_params, cell_stage_runner, compile_spec,
                           ssm_params)
from repro.core.synthesis import NetworkSpec
from repro.kernels.platform import resolve_interpret
from repro.recurrent import cells as rnn_cells

from .common import emit, time_call

# (label, spec) — paper-lstm is the acceptance config (smoke-sized: D=H=48,
# matching configs.paper_lstm.smoke_config's cell shape).
SWEEP = [
    ("paper-lstm", NetworkSpec(48, 1, 48, 48, cell="lstm", seq_len=32)),
    ("lstm-big", NetworkSpec(64, 2, 96, 32, cell="lstm", seq_len=64)),
    ("gru", NetworkSpec(48, 1, 48, 48, cell="gru", seq_len=32)),
    ("ssm", NetworkSpec(48, 1, 48, 48, cell="ssm", seq_len=32)),
    ("mlp-fig10a", NetworkSpec(8, 14, 32, 8)),
]

BATCH = 4


def _input(spec: NetworkSpec, seed: int = 0):
    shape = (BATCH, spec.num_inputs) if spec.cell == "mlp" \
        else (BATCH, spec.seq_len, spec.num_inputs)
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _handwritten_lstm_us(spec: NetworkSpec):
    """Time the hand-written fused kernel on the spec's layer-0 shapes."""
    from repro.kernels.lstm_cell import ops as lstm_ops

    D, H, T = spec.num_inputs, spec.nodes_per_layer, spec.seq_len
    p = rnn_cells.lstm_params(jax.random.PRNGKey(0), D, H)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, T, D))
    return time_call(lambda: lstm_ops.lstm_seq(
        x, p["w_x"], p["w_h"], p["b"]), warmup=2, iters=5)


def _generated_cell_us(spec: NetworkSpec):
    """Time ONE generated stage kernel on the same layer-0 shapes (the
    apples-to-apples comparison against the hand-written cell kernel)."""
    cell, D, H, T = spec.cell, spec.num_inputs, spec.nodes_per_layer, spec.seq_len
    run, graph = cell_stage_runner(cell, D, H)
    ctors = {"lstm": rnn_cells.lstm_params, "gru": rnn_cells.gru_params,
             "ssm": ssm_params}
    consts = bind_cell_params(cell, ctors[cell](jax.random.PRNGKey(0), D, H))
    x0 = {n: jnp.zeros((BATCH, w)) for n, w in graph.states.items()}
    us = jax.random.normal(jax.random.PRNGKey(1), (BATCH, T, D))
    return time_call(lambda: run(consts, x0, us), warmup=2, iters=5)


def _rtlsim_stats(spec: NetworkSpec, width: int = 16):
    """Time the bit-accurate RTL simulator (the Verilog oracle) and report
    the emitted controller's FSM cycle count — the Fig. 10 timing figure an
    actual synthesis run would check against."""
    import numpy as np

    from repro.codegen import build_program, rtlsim

    prog = build_program(spec)
    u = np.asarray(_input(spec))
    sim = rtlsim.simulate(prog, u, width=width)  # doubles as the warmup run
    t_us = time_call(lambda: rtlsim.simulate(prog, u, width=width),
                     warmup=0, iters=3)
    return t_us, sim.cycles


def run(out_dir: str = "experiments") -> list[dict]:
    rows = []
    for label, spec in SWEEP:
        px, fx = compile_spec(spec, backend="xla")
        t_xla = time_call(jax.jit(fx), px, _input(spec), warmup=1, iters=3)
        t_sim, fsm_cycles = _rtlsim_stats(spec)
        row = {"name": label, "cell": spec.cell, "batch": BATCH,
               "steps": spec.serial_steps, "xla_us": round(t_xla, 1),
               "rtlsim_us": round(t_sim, 1), "fsm_cycles": fsm_cycles}
        if spec.cell != "mlp":
            t_gen = _generated_cell_us(spec)
            row["generated_us"] = round(t_gen, 1)
            if spec.cell == "lstm":
                t_hand = _handwritten_lstm_us(spec)
                row["handwritten_us"] = round(t_hand, 1)
                row["gen_over_hand"] = round(t_gen / t_hand, 3)
        else:
            pp, fp = compile_spec(spec, backend="pallas")
            row["generated_us"] = round(
                time_call(jax.jit(fp), pp, _input(spec), warmup=1, iters=3), 1)
        rows.append(row)
        emit(f"codegen_{label}", row.get("generated_us", t_xla),
             " ".join(f"{k}={v}" for k, v in row.items() if k != "name"))

    os.makedirs(out_dir, exist_ok=True)
    fields = sorted({k for r in rows for k in r})
    with open(os.path.join(out_dir, "codegen_bench.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=fields)
        w.writeheader()
        w.writerows(rows)
    # JSON next to the bench sources — CI uploads benchmarks/*.json artifacts
    with open(os.path.join(os.path.dirname(__file__), "codegen_bench.json"), "w") as f:
        json.dump({"batch": BATCH, "interpret_mode": resolve_interpret(),
                   "rows": rows}, f, indent=2)
    return rows
