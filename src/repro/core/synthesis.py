"""The "HDL code generator" (paper §IV-D3, Table I, Fig. 10) — TPU edition.

The paper ships a C# tool that takes NN hyper-parameters through a GUI and
emits synthesizable Verilog.  The TPU-native equivalent of "emitting RTL" is
building the state-space program and lowering it through XLA: StableHLO is
the RTL, ``compiled.memory_analysis()`` is the utilization report, and the
roofline terms are the timing report.  The public API mirrors Table I
one-to-one so the correspondence is auditable:

    Create_TopModule  -> create_top_module(spec)
    Create_Layer1     -> create_layer1(...)     (input → first hidden)
    Create_Layer      -> create_layer(...)      (hidden → hidden, shared)
    Create_Layer_End  -> create_layer_end(...)  (hidden → output)
    Create_AF         -> create_af(...)         (activation function unit)
    Create_AF_End     -> create_af_end(...)
    Create_mult       -> create_mult(...)       (MACC unit)

``synthesize()`` is the push-button flow: spec → IR program → lower →
compile → report, now multi-backend (``backend="xla" | "pallas" |
"verilog"``): every spec lowers through the :mod:`repro.codegen` FSM/datapath
IR, so the XLA scan, the generated fused Pallas kernel, and the emitted
Table-I Verilog all come from the same program.  ``unroll`` and ``c_slow``
are the user's resource/speed compromise (the paper's clk_max/clk_data knob).
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_lib

from .state_space import mlp_forward, resolve_activation


# ---------------------------------------------------------------------------
# Spec — what the paper's GUI collects
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NetworkSpec:
    num_inputs: int
    num_hidden_layers: int
    nodes_per_layer: int
    num_outputs: int
    activation: str = "tanh"
    # Cell type: "mlp" is the paper's case-study feed-forward network
    # (layers-as-time); "lstm"/"gru" are the intrinsically recurrent form the
    # paper names as its flagship application (inputs-as-time, seq_len steps).
    cell: str = "mlp"
    seq_len: int = 0         # required (> 0) for recurrent cells
    # Resource/speed compromise (paper: clk_max vs clk_data):
    unroll: int = 1          # j datapath copies per scan stage
    c_slow: int = 1          # independent interleaved streams
    # Fixed-point word length used by the analysis stage (None = bf16 deploy)
    quant_bits: int | None = None
    seed: int = 0

    @property
    def name(self) -> str:
        tag = "nn" if self.cell == "mlp" else self.cell
        return (
            f"{tag}_{self.num_inputs}i_{self.num_hidden_layers}x"
            f"{self.nodes_per_layer}_{self.num_outputs}o"
        )

    @property
    def serial_steps(self) -> int:
        """Length of the time-multiplexed axis: layers for the MLP form,
        sequence steps for recurrent cells."""
        return self.num_hidden_layers if self.cell == "mlp" else self.seq_len


# ---------------------------------------------------------------------------
# Table-I module constructors
# ---------------------------------------------------------------------------

def create_mult(dtype=jnp.float32) -> Callable:
    """The MACC unit: one dot-product lane (MXU row on TPU, DSP48 on FPGA)."""

    def macc(x, w, b):
        return jnp.dot(w, x, preferred_element_type=dtype) + b

    return macc


def create_af(activation: str) -> Callable:
    """The activation-function unit for hidden nodes (shared core table)."""
    return resolve_activation(activation)


def create_af_end(activation: str = "identity") -> Callable:
    """Output-layer activation (paper: usually different from hidden)."""
    return create_af(activation)


def create_layer1(num_inputs: int, nodes: int, key) -> jnp.ndarray:
    """Input layer β: injects u into the state at k=0 (the βuδ[k] term)."""
    return jax.random.normal(key, (nodes, num_inputs)) / np.sqrt(num_inputs)


def create_layer(nodes: int, num_hidden_layers: int, key):
    """The shared hidden datapath: stacked [N, M, M] weights + [N, M] biases
    — one physical layer, N time-multiplexed uses (paper §IV-A)."""
    kw, kb = jax.random.split(key)
    W = jax.random.normal(kw, (num_hidden_layers, nodes, nodes)) / np.sqrt(nodes)
    b = 0.1 * jax.random.normal(kb, (num_hidden_layers, nodes))
    return W, b

def create_layer_end(nodes: int, num_outputs: int, key) -> jnp.ndarray:
    """Readout C: y = C x[N]."""
    return jax.random.normal(key, (num_outputs, nodes)) / np.sqrt(nodes)


def create_top_module(spec: NetworkSpec):
    """Wire the modules into the full state-space network (paper eq. 8).

    Returns (params, forward).  For the MLP form ``forward(params, u)`` maps
    a single input vector to the outputs (layers-as-time); for recurrent
    cells it maps an input *sequence* ``u: [seq_len, num_inputs]`` through
    ``spec.num_hidden_layers`` stacked cells to the readout of the final
    carry (inputs-as-time — the same shared datapath, driven by data instead
    of depth).  Batching either form is ``jax.vmap``.
    """
    key = jax.random.PRNGKey(spec.seed)
    k1, k2, k3 = jax.random.split(key, 3)

    if spec.cell != "mlp":
        if spec.seq_len <= 0:
            raise ValueError(f"recurrent spec '{spec.cell}' requires seq_len > 0")
        from repro.recurrent import cells as rnn_cells

        ctor = rnn_cells.lstm_params if spec.cell == "lstm" else rnn_cells.gru_params
        layer_keys = jax.random.split(k2, spec.num_hidden_layers)
        cell_params = [
            ctor(layer_keys[i],
                 spec.num_inputs if i == 0 else spec.nodes_per_layer,
                 spec.nodes_per_layer)
            for i in range(spec.num_hidden_layers)
        ]
        C = create_layer_end(spec.nodes_per_layer, spec.num_outputs, k3)
        params = {"cells": cell_params, "C": C}

        def forward(params, u):
            ys = u  # [T, D] time-major
            carry = None
            for cp in params["cells"]:
                carry, ys = rnn_cells.run_cell(
                    spec.cell, cp, ys, unroll=spec.unroll
                )
            h_final = carry[0] if spec.cell == "lstm" else carry
            return params["C"] @ h_final

        return params, forward

    beta = create_layer1(spec.num_inputs, spec.nodes_per_layer, k1)
    W, b = create_layer(spec.nodes_per_layer, spec.num_hidden_layers, k2)
    C = create_layer_end(spec.nodes_per_layer, spec.num_outputs, k3)
    params = {"beta": beta, "W": W, "b": b, "C": C}

    def forward(params, u):
        return mlp_forward(
            params["W"], params["b"], params["beta"], params["C"], u,
            activation_name=spec.activation, unroll=spec.unroll,
        )

    return params, forward


# ---------------------------------------------------------------------------
# synthesize(): the push-button multi-backend flow + report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SynthesisReport:
    spec: NetworkSpec
    num_params: int
    trace_lower_s: float
    compile_s: float
    hlo_bytes: int
    flops: float | None
    peak_bytes: int | None
    output_shape: tuple
    serial_depth: int
    backend: str = "xla"
    cache_hit: bool = False
    rtl: str | None = None              # backend="verilog": Table-I RTL text
    resources: Any = None               # backend="verilog": codegen.ResourceReport
    quant: dict | None = None           # quant_bits analysis (SNR / LUT mode)
    fallback_from: str | None = None    # requested backend, when degraded
    analysis: dict | None = None        # synthesize(analyze=True): the
    #                                     repro.analyze/v1 result document

    def summary(self) -> str:
        extra = ""
        if self.fallback_from is not None:
            extra += f" (fallback<-{self.fallback_from})"
        if self.quant is not None:
            snr = self.quant.get("snr_db")
            extra += f" q{self.quant['bits']}" + (
                f"={snr:.1f}dB" if snr is not None else f":{self.quant['mode']}")
        if self.rtl is not None:
            extra += f" rtl={len(self.rtl) / 1024:.1f}KiB"
        if self.cache_hit:
            extra += " (cached)"
        return (
            f"[{self.spec.name}|{self.backend}] params={self.num_params:,} "
            f"lower={self.trace_lower_s * 1e3:.1f}ms compile={self.compile_s * 1e3:.1f}ms "
            f"hlo={self.hlo_bytes / 1024:.1f}KiB flops={self.flops} "
            f"peak_bytes={self.peak_bytes} depth={self.serial_depth}{extra}"
        )


# Memoization: Fig. 10-style sweeps re-synthesize identical specs; one trace +
# compile per cache key is enough.  NetworkSpec is frozen/hashable.
_SYNTH_CACHE: dict[tuple, SynthesisReport] = {}


def _cache_key(spec: NetworkSpec, batch: int | None, backend: str,
               double_buffer: bool, chunk: int | None = None,
               block_b: int | None = None, mesh=None) -> tuple:
    """EVERY knob that changes the compiled artifact must appear here.

    ``spec`` is a frozen dataclass, so its hash covers the shape knobs AND
    ``quant_bits`` (which derives the pallas lut/int8-MACC modes — the
    ``int8_macc`` flag is ``backend=="pallas" and quant_bits<=8``, a pure
    function of key fields, so it cannot alias).  ``double_buffer`` /
    ``chunk`` / ``block_b`` only exist on the pallas backend; normalize
    them for the others so an xla/verilog call can't fork the cache on an
    irrelevant flag.  ``mesh`` keys by the ShardPlan identity (axis names +
    shape + device ids) on the backends that consume it — two different
    meshes never alias, and mesh is normalized away where it has no effect.
    """
    if backend != "pallas":
        double_buffer, chunk, block_b = True, None, None
    mesh_key = None
    if mesh is not None and backend in ("xla", "pallas"):
        from repro.runtime.shard_plan import ShardPlan

        mesh_key = ShardPlan(mesh).key()
    return (spec, batch, backend, double_buffer, chunk, block_b, mesh_key)


def synthesize_cache_clear() -> None:
    _SYNTH_CACHE.clear()


def synthesize_cache_info() -> dict:
    return {"entries": len(_SYNTH_CACHE)}


def _quant_analysis(spec: NetworkSpec, backend: str, prog) -> dict | None:
    """Honor ``spec.quant_bits`` (paper stage 3, Fig. 11).

    mlp: bit-exact fixed-point simulation vs double reference → output SNR.
    recurrent + pallas: gate activations switch to the ROM-LUT kernel path;
    ``quant_bits <= 8`` additionally runs every gate contraction on the
    int8 MACC datapath (per-channel-scaled fixed-point weights — the paper's
    DSP datapath), which also covers af-free cells like the ssm.
    recurrent + xla: unsupported — raise rather than silently ignore.
    (verilog always honors quant_bits as the RTL word width.)
    """
    if spec.quant_bits is None:
        return None
    int8_macc = backend == "pallas" and spec.quant_bits <= 8
    if spec.cell == "mlp":
        from .quantization import snr_sweep

        sp = prog.stages[0].params
        W = np.swapaxes(np.asarray(sp["W"], np.float64), -1, -2)
        b = np.asarray(sp["b"], np.float64)[:, 0, :]
        beta = np.asarray(prog.beta, np.float64)
        C = np.asarray(prog.C, np.float64)
        [(bits, snr)] = snr_sweep(W, b, beta, C, [spec.quant_bits],
                                  num_inputs=128, seed=spec.seed)
        return {"bits": bits, "mode": "fixed-point", "int8_macc": int8_macc,
                "snr_db": float(np.mean(snr)),
                "per_output_snr_db": [float(s) for s in snr]}
    has_af = any(st.graph.af_nodes() for st in prog.stages)
    if backend == "pallas" and has_af:  # ssm has no af units to quantize
        return {"bits": spec.quant_bits, "mode": "lut", "int8_macc": int8_macc}
    if int8_macc:  # af-free cells still have MACC units to quantize
        return {"bits": spec.quant_bits, "mode": "int8", "int8_macc": True}
    if backend == "verilog":
        return {"bits": spec.quant_bits, "mode": "rtl-width"}
    raise ValueError(
        f"quant_bits={spec.quant_bits} with cell='{spec.cell}' is not supported "
        f"on backend='{backend}' — use backend='pallas' on a cell with "
        "activation units (ROM-LUT gates) or quant_bits<=8 (int8 MACC), "
        "backend='verilog' (RTL word width), or cell='mlp' (fixed-point SNR)"
    )


def _ledger_key(spec: NetworkSpec, batch: int | None, backend: str,
                double_buffer: bool = True, chunk: int | None = None,
                block_b: int | None = None, mesh=None) -> str:
    """Program id in the predicted-vs-measured ledger: one row per distinct
    compiled artifact the Fig. 10 loop could rank.  Non-default pallas
    tiling knobs get their own tags so tuner candidates never collide."""
    key = f"{spec.name}|{backend}|u{spec.unroll}|c{spec.c_slow}"
    if spec.quant_bits is not None:
        key += f"|q{spec.quant_bits}"
    if batch:
        key += f"|b{batch}"
    if backend == "pallas":
        if not double_buffer:
            key += "|db0"
        if chunk is not None:
            key += f"|ch{chunk}"
        if block_b is not None:
            key += f"|bb{block_b}"
    if mesh is not None and backend in ("xla", "pallas"):
        from repro.runtime.shard_plan import ShardPlan

        plan = ShardPlan(mesh)
        key += f"|mesh{plan.dp}x{plan.tp}"
    return key


def _analyze_compiled(fwd, params, u: jax.ShapeDtypeStruct):
    """lower → compile → (timings, hlo bytes, flops, peak bytes, compiled)."""
    tr = obs_lib.OBS.tracer
    t0 = time.perf_counter()
    with tr.span("synth.lower", cat="synth"):
        lowered = jax.jit(fwd).lower(params, u)
    t1 = time.perf_counter()
    with tr.span("synth.compile", cat="synth"):
        compiled = lowered.compile()
    t2 = time.perf_counter()
    try:
        from repro.kernels._compat import first_cost_analysis

        cost = first_cost_analysis(compiled)
        # None (not NaN) when the backend reports nothing — keeps the
        # `if flops` / `is None` consumers honest (NaN is truthy)
        flops = float(cost["flops"]) if "flops" in cost else None
    except Exception:  # noqa: BLE001 — cost analysis is advisory
        flops = None
    try:
        mem = compiled.memory_analysis()
        peak = int(getattr(mem, "temp_size_in_bytes", 0)) + int(
            getattr(mem, "argument_size_in_bytes", 0)
        )
    except Exception:  # noqa: BLE001 — memory analysis is advisory
        peak = None
    return t1 - t0, t2 - t1, len(lowered.as_text()), flops, peak, compiled


def _measure_compiled(compiled, params, u_shape, key: str) -> None:
    """Time one real execution of the compiled program (warmup + best-of-2)
    into the process ledger — the *measured* column of the Fig. 10 loop,
    taken through the same span layer the serving stack uses."""
    O = obs_lib.OBS
    u0 = np.zeros(u_shape, np.float32)
    try:
        with O.tracer.span("synth.measure", cat="synth",
                           args={"program": key}):
            jax.block_until_ready(compiled(params, u0))      # warmup
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(params, u0))
                O.ledger.measure(key, time.perf_counter() - t0)
    except Exception:  # noqa: BLE001
        # measurement is telemetry, never a synthesis failure (e.g. AOT
        # executables that reject host arrays on exotic backends)
        pass


# Degradation order when a backend's compile step keeps failing with a
# TransientFault (any other error is re-raised): the fused
# pallas kernel falls back to the plain XLA scan, and that falls back to the
# unlowered reference forward ("ref": create_top_module + vmap — no codegen
# IR in the compile path at all).  verilog's *compiled* artifact is the XLA
# program, so it degrades straight to ref (RTL emission is unaffected).
_SYNTH_FALLBACK: dict[str, tuple[str, ...]] = {
    "pallas": ("xla", "ref"),
    "xla": ("ref",),
    "verilog": ("ref",),
    "ref": (),
}


def _faults_mod():
    """The ambient fault-injection module, WITHOUT importing the runtime
    package: if ``repro.runtime.faults`` was never imported, no plan can be
    installed and there is nothing to consult."""
    return sys.modules.get("repro.runtime.faults")


def _is_transient(exc: BaseException) -> bool:
    m = _faults_mod()
    return m is not None and isinstance(exc, m.TransientFault)


def _build_fwd(program, spec: NetworkSpec, backend: str, quant: dict | None,
               double_buffer: bool, chunk: int | None, block_b: int | None,
               mesh=None):
    """One backend's (fwd, params) — the compile target for the retry /
    fallback loop in :func:`synthesize`.  ``mesh`` threads the device mesh
    into the xla (GSPMD TP/DP constraints) and pallas (shard_map over DP)
    backends; "ref" and "verilog" ignore it."""
    from repro import codegen

    m = _faults_mod()
    if m is not None:
        m.maybe_raise("synth.compile")

    if backend == "ref":
        ref_params, ref_fwd = create_top_module(spec)
        fwd = jax.vmap(ref_fwd, in_axes=(None, 0))
        if spec.c_slow > 1:
            fwd = jax.vmap(fwd, in_axes=(None, 0))
        return fwd, ref_params

    lut = None
    if quant is not None and quant["mode"] == "lut":
        from repro.kernels.tanh_lut.ref import make_lut

        lut = make_lut(min(max(spec.quant_bits // 2, 6), 10))
    params = program.params
    if backend == "pallas":
        int8_bits = spec.quant_bits if quant and quant.get("int8_macc") else None
        pb = codegen.pallas_backend
        fwd = pb.compile_program(
            program, lut=lut, quant_bits=int8_bits,
            double_buffer=double_buffer,
            chunk=chunk if chunk is not None else pb.DEFAULT_CHUNK,
            block_b=block_b if block_b is not None else pb.DEFAULT_BLOCK_B,
            mesh=mesh)
        if int8_bits is not None:
            # pack the int8 weight ROM pages ONCE, here at synthesis time —
            # the kernel then streams 1/4-size pages through the double
            # buffer with the dequant fused after the dot, instead of
            # re-quantizing inside every traced call
            params = dict(params)
            params["stages"] = [
                pb.prequantize_consts(st.graph, sp, int8_bits)
                for st, sp in zip(program.stages, params["stages"])]
        return fwd, params
    # "xla" and the verilog cross-check both compile the XLA program
    xmesh = mesh if backend == "xla" else None
    return codegen.xla_backend.compile_program(program, mesh=xmesh), params


def _static_gate(spec: NetworkSpec, program, waivers, O) -> dict:
    """``synthesize(analyze=True)``: run :mod:`repro.analyze` on the IR and
    raise :class:`repro.analyze.AnalysisError` on unwaived error findings —
    purely static, before (and regardless of) any backend compile."""
    from repro import codegen
    from repro.analyze import analyze_program, gate

    if program is None:                 # cache-hit path: rebuild (cheap)
        program = codegen.build_program(spec)
    with O.tracer.span("synth.analyze", cat="synth",
                       args={"spec": spec.name}):
        res = analyze_program(program, waivers=waivers)
    O.metrics.counter("synth_analyze", "synthesize(analyze=True) gate runs",
                      result="fail" if res.errors else "pass").inc()
    gate(res)
    return res.to_doc()


def synthesize(spec: NetworkSpec, batch: int | None = None,
               backend: str = "xla", *,
               mesh=None,
               double_buffer: bool = True,
               chunk: int | None = None,
               block_b: int | None = None,
               measure: bool = True,
               optimize: str | None = None,
               budget: int | None = None,
               retries: int = 2,
               backoff_s: float = 0.05,
               fallback: bool = True,
               analyze: bool = False,
               waivers=None):
    """spec → IR program → {XLA scan, fused Pallas kernel, Verilog RTL}.

    All backends consume the same :mod:`repro.codegen` program, so
    ``backend="xla"`` and ``backend="pallas"`` are output-equivalent and
    ``backend="verilog"`` additionally attaches the Table-I RTL text plus a
    resource report cross-checked against ``compiled.cost_analysis()``.
    ``double_buffer`` forwards to the pallas backend (2-slot ROM prefetch
    vs BlockSpec streaming); ``chunk`` / ``block_b`` override its tiling
    block params.  Results are memoized by :func:`_cache_key`.

    ``mesh`` (a ``jax.sharding.Mesh``) makes the compiled artifact
    mesh-aware: the xla backend pins the input batch/stream axis over the
    DP axes and row-parallels the gate-weight ROMs over ``"model"`` (GSPMD
    places the all-reduce at the gate nonlinearity); the pallas backend
    shard_maps the folded C-slow × batch grid over the DP axes.  The cache
    and ledger key on the mesh identity, so single-device and mesh
    artifacts never alias.

    ``optimize="latency" | "throughput" | "resources"`` runs the paper's
    Fig. 10 optimization loop instead of one fixed synthesis: the
    :mod:`repro.tune` auto-tuner searches the knob space around ``spec``
    (unroll × c_slow × quant_bits × double_buffer × backend × tiling),
    measures the top-``budget`` predicted candidates, difftest-validates
    the winner, and returns a :class:`repro.tune.TuneResult` whose
    ``.report`` is the winning configuration's SynthesisReport.

    Every first-time synthesis feeds the process observability scope
    (:data:`repro.obs.OBS`): compile/cache-hit spans and counters, plus a
    predicted-vs-measured ledger row joining the rtlsim FSM cycle estimate
    and ``cost_analysis`` flops against measured wall-clock
    (``measure=False`` skips the timed execution).

    Robustness: a transient compile failure (a ``TransientFault``, e.g. an
    injected ``synth.compile`` fault) is retried up to ``retries`` times
    with exponential ``backoff_s`` backoff; a backend that keeps failing
    transiently degrades down the pallas → xla → ref chain
    (``fallback=False`` re-raises instead).  Any other error — a kernel the
    device's compiler refuses, a shape bug — is re-raised at once, so a
    report never hides which backend really ran.  The returned report's
    ``backend`` is the backend that actually compiled; ``fallback_from``
    records the requested one, and the
    ``synth_retries`` / ``synth_fallback{from_backend,to}`` counters track
    both events.

    ``analyze=True`` runs the :mod:`repro.analyze` static range/overflow +
    hazard analysis on the IR *before* any backend compile and raises
    :class:`repro.analyze.AnalysisError` on unwaived error-grade findings
    (pass a :class:`repro.analyze.WaiverRegistry` as ``waivers`` to
    acknowledge known ones); the ``repro.analyze/v1`` result document is
    attached as ``report.analysis``.  The gate is opt-in and outside the
    memo key — a cache hit re-attaches a fresh analysis.
    """
    from repro import codegen

    if optimize is not None:
        from repro.tune import tune

        return tune(spec, optimize=optimize, budget=budget, batch=batch)

    O = obs_lib.OBS
    if backend != "ref" and backend not in codegen.BACKENDS:
        raise ValueError(
            f"unknown backend '{backend}'; available: {codegen.BACKENDS}")
    key = _cache_key(spec, batch, backend, double_buffer, chunk, block_b,
                     mesh)
    if key in _SYNTH_CACHE:
        O.metrics.counter("synth_cache", "synthesize() memo", result="hit").inc()
        report = dataclasses.replace(_SYNTH_CACHE[key], cache_hit=True)
        if analyze:    # the gate is outside the memo key: re-run, re-attach
            report = dataclasses.replace(
                report, analysis=_static_gate(spec, None, waivers, O))
        return report
    O.metrics.counter("synth_cache", "synthesize() memo", result="miss").inc()

    with O.tracer.span("synth.build_program", cat="synth",
                       args={"spec": spec.name, "backend": backend}):
        program = codegen.build_program(spec)
    analysis_doc = (_static_gate(spec, program, waivers, O)
                    if analyze else None)
    # the REQUESTED backend's quant validation still raises on unsupported
    # combinations (user error, not a fault to degrade around)
    quant = _quant_analysis(spec, backend, program)

    u_shape = (spec.num_inputs,) if spec.cell == "mlp" \
        else (spec.seq_len, spec.num_inputs)
    u_shape = (batch or 1,) + u_shape
    if spec.c_slow > 1:  # C interleaved streams through the one datapath
        u_shape = (spec.c_slow,) + u_shape
    u = jax.ShapeDtypeStruct(u_shape, jnp.float32)

    chain = (backend,) + (_SYNTH_FALLBACK.get(backend, ())
                          if fallback else ())
    analysis = None
    used = backend
    last_err: BaseException | None = None
    for hop, bk in enumerate(chain):
        if hop:
            O.metrics.counter(
                "synth_fallback", "backend fallback hops",
                from_backend=chain[hop - 1], to=bk).inc()
            try:
                quant = _quant_analysis(spec, bk, program)
            except ValueError:
                quant = None    # degraded: quant not expressible here
        for attempt in range(max(0, retries) + 1):
            try:
                fwd, bparams = _build_fwd(program, spec, bk, quant,
                                          double_buffer, chunk, block_b,
                                          mesh)
                analysis = _analyze_compiled(fwd, bparams, u)
                break
            except Exception as e:  # noqa: BLE001 — only transients degrade
                if not _is_transient(e):
                    raise   # a real compile error must not hide behind a fallback
                last_err = e
                if attempt < retries:
                    O.metrics.counter("synth_retries",
                                      "transient compile retries").inc()
                    if backoff_s > 0:
                        time.sleep(backoff_s * (2 ** attempt))
                    continue
                break   # retries exhausted: next backend
        if analysis is not None:
            used = bk
            break
    if analysis is None:
        raise last_err
    lower_s, compile_s, hlo_bytes, flops, peak, compiled = analysis
    params = bparams

    # predicted-vs-measured ledger: the Fig. 10 loop's instrumentation
    lkey = _ledger_key(spec, batch, used, double_buffer, chunk, block_b,
                       mesh)
    O.ledger.predict(
        lkey,
        fsm_cycles=codegen.rtlsim.fsm_cycle_estimate(program),
        flops=flops, peak_bytes=peak, hlo_bytes=hlo_bytes,
        compile_s=compile_s, num_params=program.num_params(),
    )
    if measure:
        _measure_compiled(compiled, params, u_shape, lkey)

    rtl = resources = None
    if backend == "verilog":
        rtl = codegen.emit_program(program)
        resources = codegen.report_program(program)
        resources.xla_flops = flops          # the cost_analysis cross-check
        resources.xla_peak_bytes = peak

    from .transition import serial_depth_estimate

    report = SynthesisReport(
        spec=spec,
        num_params=program.num_params(),
        trace_lower_s=lower_s,
        compile_s=compile_s,
        hlo_bytes=hlo_bytes,
        flops=flops,
        peak_bytes=peak,
        # the true compiled output shape: always batched, stream axis when C>1
        output_shape=(u_shape[:-1] if spec.cell == "mlp" else u_shape[:-2])
        + (spec.num_outputs,),
        serial_depth=serial_depth_estimate(
            spec.serial_steps * spec.c_slow, spec.unroll),
        backend=used,
        fallback_from=backend if used != backend else None,
        quant=quant,
        rtl=rtl,
        resources=resources,
    )
    _SYNTH_CACHE[key] = report
    if analysis_doc is not None:
        return dataclasses.replace(report, analysis=analysis_doc)
    return report
