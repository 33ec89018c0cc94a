"""Pallas backend: IR → ONE generated fused scan-step kernel.

Generalizes the hand-written ``kernels/lstm_cell`` pattern to any datapath
graph: grid ``(B/bb, T/ct)`` with the batch axis parallel and the chunk
axis sequential; every state register is a VMEM scratch that persists
across chunks (the paper's eq. 1 state register, never spilled to HBM
between chunks); within a chunk the ``ct`` steps are one loop over
time-major ``[ct, bb, ·]`` blocks, so the kernel's code size (and its
compile time) is one step's, not ``ct`` steps'; the graph is evaluated per
step by the SAME ``ir.eval_graph`` the XLA backend uses — macc nodes hit
the MXU, gate algebra the VPU.

VMEM: shared weight ROMs are resident and single-buffered (their block
index never changes), and the kernel asks Mosaic for the scoped VMEM its
blocks, buffers and scratch actually add up to — a ``[2048, 4096]`` f32
gate ROM (paper-lstm's D=H=1024) is 32 MiB on its own, twice Mosaic's
default limit.

Ragged shapes: ``B`` and ``T`` are padded up to the block/chunk multiple and
the padded tail steps are masked out of the state update, so prime-sized
batches and sequence lengths run the SAME tiling as round ones instead of
degrading to 1-wide blocks (or crashing).

Const ROMs: shared consts are resident whole; per-step consts (the MLP's
stacked W[k] pages) live in HBM (``memory_space=ANY``) and are **double
buffered**: while the datapath computes chunk t, an async DMA prefetches
chunk t+1's ROM pages into the second half of a 2-slot VMEM scratch — the
operand-streaming idiom every FPGA-accelerator survey names alongside loop
pipelining (and the reason the FSM never stalls on coefficient fetch).
``double_buffer=False`` falls back to BlockSpec streaming for A/B timing.

Quantized paths (paper §IV-B):
  * ``lut`` switches tanh/sigmoid to the shared ROM-LUT idiom of
    ``kernels/_lut`` (one-hot × table MXU contractions with linear
    interpolation; σ(x) = (1 + tanh(x/2))/2 reuses the same table).
  * ``quant_bits <= 8`` switches every 2-D weight ROM feeding a macc node to
    weight-only int8: the ROM pages are packed ONCE (at synthesis time via
    :func:`prequantize_consts`, or on the first traced call) to int8 codes
    plus a per-output-channel scale, ship through the double-buffer DMA at
    1/4 the bytes, and the dequant is fused into the Q-align select after
    the dot (``(x @ w_q) * scale`` — exact, because the scale is
    per-output-channel) — the paper's fixed-point coefficient ROM, composing
    with the LUT gates.  Activations stay f32: the earlier dynamic per-row
    activation quantization re-quantized every step and pushed the MACC
    onto an int32 dot with no fast path, which made int8 *slower* than f32.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs as obs_lib
from repro.core.state_space import ACTIVATIONS
from repro.kernels._lut import lut_interpolate, shifted_table
from repro.kernels.int8_matmul.ops import quantize_per_channel
from repro.kernels.platform import resolve_interpret, vmem_bytes, vmem_limit

from .ir import DatapathGraph, Program, Stage, eval_graph

PyTree = Any

DEFAULT_CHUNK = 32
DEFAULT_BLOCK_B = 8


def _act_resolver(lut_refs, n_lut: int) -> Callable:
    """Activation resolver for kernel bodies: LUT tanh/sigmoid when a table
    is loaded, VPU transcendentals otherwise."""
    if n_lut:
        lut = lut_refs[0][0, :]
        lut1 = lut_refs[1][0, :]
        tanh = lambda v: lut_interpolate(v, lut, lut1, n_lut)
    else:
        tanh = jnp.tanh
    sig = lambda v: 0.5 * (1.0 + tanh(0.5 * v))
    table = dict(ACTIVATIONS)
    table["tanh"] = tanh
    table["sigmoid"] = sig

    def act(fn: str):
        return table[fn]

    return act


def prequantize_consts(graph: DatapathGraph, consts: dict,
                       quant_bits: int | None) -> dict:
    """Pack every quantizable weight ROM to int8 ONCE, at synthesis time.

    Returns a new consts dict where each ``graph.quantizable_weights()``
    entry is replaced by its int8 codes and a ``"<name>.scale"`` companion
    carries the per-output-channel scale (``quantize_per_channel`` keepdims
    layout; for per-step ROM stacks the leading T axis is preserved, one
    scale bank per page).  ``compile_stage``'s ``run()`` recognizes packed
    consts by the ``.scale`` companion and streams the int8 pages as-is —
    no per-call quantization work, and the double-buffer DMA moves 1/4 the
    bytes.  Unpacked float consts keep working (they are quantized inside
    the trace, once per jit cache entry), so callers that re-bind trained
    weights every call lose nothing.
    """
    if quant_bits is None or quant_bits > 8:
        return consts
    out = dict(consts)
    for name in graph.quantizable_weights():
        if name not in out or f"{name}.scale" in out:
            continue  # absent (bound later) or already packed
        w_q, s = quantize_per_channel(
            jnp.asarray(out[name], jnp.float32), axis=-2)
        out[name] = w_q
        out[f"{name}.scale"] = s
    return out


def _pad_to(arr, size: int, axis: int):
    """Zero-pad ``arr`` up to ``size`` along ``axis`` (no-op when equal)."""
    if arr.shape[axis] == size:
        return arr
    pads = [(0, 0)] * arr.ndim
    pads[axis] = (0, size - arr.shape[axis])
    return jnp.pad(arr, pads)


def compile_stage(stage: Stage, *, lut=None, chunk: int = DEFAULT_CHUNK,
                  block_b: int = DEFAULT_BLOCK_B,
                  interpret: bool | None = None,
                  quant_bits: int | None = None,
                  double_buffer: bool = True) -> Callable:
    """Generate the fused kernel for one scheduled datapath.

    Returns ``run(consts, x0, us) -> (final_states, ys)`` with ``x0`` leaves
    ``[B, width]`` and ``us`` ``[B, T, D]`` (None for autonomous graphs).
    Any ``B``/``T`` is accepted (padded + masked internally).
    """
    graph, sched = stage.graph, stage.schedule
    state_names = sorted(graph.states)
    per_step = [n.name for n in graph.consts(per_step=True)]
    shared_names = [n.name for n in graph.consts(per_step=False)]
    inp = graph.input_node()
    has_out = graph.output is not None
    out_width = graph.node(graph.output).width if has_out else 0
    n_state = len(state_names)
    n_lut = 0 if lut is None else int(lut.shape[0])
    itp = resolve_interpret(interpret)
    int8 = quant_bits is not None and quant_bits <= 8
    qnames = set(graph.quantizable_weights()) if int8 else set()
    ps_q = [n for n in per_step if n in qnames]       # streamed int8 ROMs
    sh_q = [n for n in shared_names if n in qnames]   # resident int8 ROMs
    # double-buffered stream set: per-step ROM pages + their scale pages
    stream_names = per_step + [f"{n}.scale" for n in ps_q]

    # Compile-time-only observability: count generated stages and annotate
    # the ROM-prefetch configuration.  NEVER trace inside kernel()/run() —
    # they execute under jit, where a host-side tracer would either leak
    # into the jaxpr or force a sync.
    _O = obs_lib.OBS
    _O.metrics.counter(
        "pallas_stages_compiled", "fused stage kernels generated",
        quantized=str(bool(int8)).lower()).inc()
    _O.tracer.instant(
        "pallas.compile_stage", cat="codegen",
        args={"per_step_roms": len(per_step), "streamed_pages": len(stream_names),
              "double_buffer": bool(double_buffer and per_step),
              "states": n_state, "unroll": sched.unroll, "c_slow": sched.c_slow})

    def kernel(*refs, ct: int, num_chunks: int, t_total: int):
        db = double_buffer and bool(per_step)
        i = 0
        x_ref = refs[i] if inp is not None else None
        i += 1 if inp is not None else 0
        ps_refs = {name: refs[i + j] for j, name in enumerate(per_step)}
        i += len(per_step)
        ps_scale = {name: refs[i + j] for j, name in enumerate(ps_q)}
        i += len(ps_q)
        sh_refs = {name: refs[i + j] for j, name in enumerate(shared_names)}
        i += len(shared_names)
        sh_scale = {name: refs[i + j] for j, name in enumerate(sh_q)}
        i += len(sh_q)
        s0_refs = {name: refs[i + j] for j, name in enumerate(state_names)}
        i += n_state
        lut_refs = refs[i: i + (2 if n_lut else 0)]
        i += 2 if n_lut else 0
        y_ref = refs[i] if has_out else None
        i += 1 if has_out else 0
        fin_refs = {name: refs[i + j] for j, name in enumerate(state_names)}
        i += n_state
        scr = {name: refs[i + j] for j, name in enumerate(state_names)}
        i += n_state
        dq_scr = {name: refs[i + j] for j, name in enumerate(sh_q)}
        i += len(sh_q)
        if db:
            stream_scr = {name: refs[i + j]
                          for j, name in enumerate(stream_names)}
            i += len(stream_names)
            dma_sem = refs[i]

        ci = pl.program_id(1)

        @pl.when(ci == 0)
        def _init():
            for name in state_names:
                scr[name][...] = s0_refs[name][...].astype(jnp.float32)
            for name in sh_q:
                # dequant the WHOLE shared int8 ROM once per batch block
                # into f32 scratch ((x @ w_q)·s ≡ x @ (w_q·s), per-output-
                # channel s), so the per-step MACC is the same plain f32
                # dot as the fp32 path — only per-step DMA'd pages pay a
                # fused post-dot rescale
                dq_scr[name][...] = (sh_refs[name][...].astype(jnp.float32)
                                     * sh_scale[name][...])

        def hbm_of(name):
            return ps_scale[name[:-6]] if name.endswith(".scale") else ps_refs[name]

        if db:
            # Double-buffered ROM streaming: chunk c's pages live in VMEM
            # slot c%2; chunk c+1's DMA is issued BEFORE waiting on chunk c,
            # so the fetch overlaps the datapath work below.
            def dma(j, name, idx, slot):
                return pltpu.make_async_copy(
                    hbm_of(name).at[pl.ds(idx * ct, ct)],
                    stream_scr[name].at[slot], dma_sem.at[j, slot])

            @pl.when(ci == 0)
            def _warm():
                for j, name in enumerate(stream_names):
                    dma(j, name, 0, 0).start()

            @pl.when(ci + 1 < num_chunks)
            def _prefetch():
                nxt = jax.lax.rem(ci + 1, 2)
                for j, name in enumerate(stream_names):
                    dma(j, name, ci + 1, nxt).start()

            slot = jax.lax.rem(ci, 2)
            for j, name in enumerate(stream_names):
                dma(j, name, ci, slot).wait()

        def page(name, t):
            """Per-step ROM page t of the current chunk."""
            return stream_scr[name][slot, t] if db else hbm_of(name)[t]

        act = _act_resolver(lut_refs, n_lut)
        ragged = num_chunks * ct != t_total

        def step(t, states):
            u_t = x_ref[t].astype(jnp.float32) if inp is not None else None

            def consts_get(name):
                if name in per_step:
                    return page(name, t)
                return (dq_scr if name in dq_scr else sh_refs)[name][...]

            def mm(x, w_name, w):
                if w_name not in ps_q:
                    return x @ w    # fp32, or shared int8 dequanted above
                # weight-only int8 page: f32 activations × int8 codes,
                # dequant fused into the Q-align select AFTER the dot —
                # exact because the scale is per-output-channel ([1, N]
                # broadcast over the [B, N] product).  The page arrived
                # int8 from the DMA (1/4 the bytes) and casts here.
                s_w = page(f"{w_name}.scale", t)
                return (x @ w.astype(jnp.float32)) * s_w

            new_states, y = eval_graph(graph, consts=consts_get, states=states,
                                       u=u_t, act=act, mm=mm)
            if ragged:
                # ragged T: padded tail steps must not advance the registers
                valid = ci * ct + t < t_total
                new_states = {k: jnp.where(valid, new_states[k], states[k])
                              for k in new_states}
            if has_out:
                y_ref[t] = y.astype(y_ref.dtype)
            return new_states

        states = jax.lax.fori_loop(
            0, ct, step, {name: scr[name][...] for name in state_names})
        for name in state_names:
            scr[name][...] = states[name]

        @pl.when(ci == num_chunks - 1)
        def _fin():
            for name in state_names:
                fin_refs[name][...] = states[name]

    def run(consts: dict, x0: dict, us):
        B = x0[state_names[0]].shape[0]
        T = us.shape[1] if us is not None else sched.steps
        # pad-and-mask tiling: ragged B/T keep the full-width blocks
        ct = min(max(chunk, sched.unroll), T)
        bb = min(block_b, B)
        Tp = -(-T // ct) * ct
        Bp = -(-B // bb) * bb
        num_chunks = Tp // ct
        db = double_buffer and bool(per_step)

        vmem = 0    # bytes of every VMEM buffer below → the scoped limit

        def block(shape, index_map, dtype=jnp.float32, *, invariant=False):
            """A VMEM block; grid-invariant blocks (weight ROMs, LUTs) are
            single-buffered — fetched once, never re-streamed."""
            nonlocal vmem
            vmem += vmem_bytes(shape, dtype) * (1 if invariant else 2)
            if invariant:
                return pl.BlockSpec(shape, index_map,
                                    pipeline_mode=pl.Buffered(1))
            return pl.BlockSpec(shape, index_map)

        def scratch(shape, dtype=jnp.float32):
            nonlocal vmem
            vmem += vmem_bytes(shape, dtype)
            return pltpu.VMEM(shape, dtype)

        in_specs, operands = [], []
        if inp is not None:
            D = inp.width
            in_specs.append(block((ct, bb, D), lambda i, c: (c, i, 0)))
            us_tm = jnp.swapaxes(jnp.asarray(us, jnp.float32), 0, 1)
            operands.append(_pad_to(_pad_to(us_tm, Tp, 0), Bp, 1))

        def add_stream(arr):
            """Per-step operand: resident in ANY/HBM when double-buffered
            (the kernel DMAs chunk slices itself), BlockSpec-chunked else."""
            if db:
                in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            else:
                tail = arr.shape[1:]
                in_specs.append(block(
                    (ct,) + tail, lambda i, c, nd=len(tail): (c,) + (0,) * nd,
                    arr.dtype))
            operands.append(arr)

        def packed(name):
            """int8 codes + scale for a quantizable ROM: pre-packed consts
            (``prequantize_consts`` synthesis-time packing, recognized by
            the ``.scale`` companion) pass through untouched; raw float
            consts are quantized here, inside the trace."""
            if f"{name}.scale" in consts:
                return (jnp.asarray(consts[name]),
                        jnp.asarray(consts[f"{name}.scale"], jnp.float32))
            return quantize_per_channel(
                jnp.asarray(consts[name], jnp.float32), axis=-2)

        ps_scales = {}
        for name in per_step:
            if name in qnames:  # [T, ...] int8 pages: 1/4 the DMA bytes
                arr, ps_scales[name] = packed(name)
            else:
                arr = jnp.asarray(consts[name], jnp.float32)
            add_stream(_pad_to(arr, Tp, 0))
        for name in ps_q:
            add_stream(_pad_to(ps_scales[name], Tp, 0))
        sh_scales, sh_shapes = {}, {}
        for name in shared_names:
            if name in qnames:
                arr, sh_scales[name] = packed(name)
            else:
                arr = jnp.asarray(consts[name], jnp.float32)
            sh_shapes[name] = arr.shape
            in_specs.append(block(arr.shape,
                                  lambda i, c, nd=arr.ndim: (0,) * nd,
                                  arr.dtype, invariant=True))
            operands.append(arr)
        for name in sh_q:
            arr = sh_scales[name]
            in_specs.append(block(arr.shape,
                                  lambda i, c, nd=arr.ndim: (0,) * nd,
                                  invariant=True))
            operands.append(arr)
        for name in state_names:
            w = graph.states[name]
            in_specs.append(block((bb, w), lambda i, c: (i, 0)))
            operands.append(_pad_to(jnp.asarray(x0[name], jnp.float32), Bp, 0))
        if n_lut:
            lut1 = shifted_table(lut)
            in_specs += [block((1, n_lut), lambda i, c: (0, 0), invariant=True)
                         for _ in range(2)]
            operands += [jnp.asarray(lut, jnp.float32)[None],
                         jnp.asarray(lut1, jnp.float32)[None]]

        out_specs, out_shape = [], []
        if has_out:
            out_specs.append(block((ct, bb, out_width), lambda i, c: (c, i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((Tp, Bp, out_width), jnp.float32))
        for name in state_names:
            w = graph.states[name]
            out_specs.append(block((bb, w), lambda i, c: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((Bp, w), jnp.float32))

        scratch_shapes = [scratch((bb, graph.states[n])) for n in state_names]
        # f32 dequant of each shared int8 ROM, filled once per batch block
        scratch_shapes += [scratch(sh_shapes[n]) for n in sh_q]
        if db:
            # the 2-slot prefetch buffers + one DMA semaphore per (stream, slot)
            for j, name in enumerate(stream_names):
                src = operands[(1 if inp is not None else 0) + j]
                scratch_shapes.append(
                    scratch((2, ct) + src.shape[1:], src.dtype))
            scratch_shapes.append(pltpu.SemaphoreType.DMA((len(stream_names), 2)))

        results = pl.pallas_call(
            functools.partial(kernel, ct=ct, num_chunks=num_chunks, t_total=T),
            grid=(Bp // bb, num_chunks),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=vmem_limit(vmem),
            ),
            interpret=itp,
        )(*operands)

        o = 0
        ys = None
        if has_out:
            ys, o = jnp.swapaxes(results[0][:T, :B], 0, 1), 1
        finals = {name: results[o + j][:B] for j, name in enumerate(state_names)}
        return finals, ys

    return run


def compile_program(program: Program, *, lut=None,
                    chunk: int = DEFAULT_CHUNK, block_b: int = DEFAULT_BLOCK_B,
                    interpret: bool | None = None,
                    quant_bits: int | None = None,
                    double_buffer: bool = True, mesh=None) -> Callable:
    """IR → batched forward through generated fused kernels — the same
    signature as :func:`xla_backend.compile_program`.

    ``c_slow = C > 1`` folds the stream axis into the batch grid axis
    (:func:`repro.core.cslow.fold_streams`): the kernel's batch dimension IS
    the C-slow interleave — ONE fused kernel launch carries all C·B streams
    through the one datapath, instead of ``cslow_vectorized``'s
    vmap-of-scans.  ``quant_bits <= 8`` runs every gate contraction on the
    weight-only int8 ROM path (see :func:`compile_stage` /
    :func:`prequantize_consts`).

    With ``mesh`` the forward runs under ``shard_map`` over the mesh's DP
    axes: the leading (stream/batch) axis splits across data shards and
    each shard folds its LOCAL streams into its own kernel grid —
    ``c_slow × data_shards`` compose on the same batch dimension (ROM
    double-buffering stays per-device; params replicate).  A leading axis
    that doesn't divide the DP size is zero-padded up to it (the padded
    streams are independent and sliced off), so every data shard runs.
    """
    from repro.core.cslow import fold_streams, unfold_streams

    program.validate()
    runners = [compile_stage(st, lut=lut, chunk=chunk, block_b=block_b,
                             interpret=interpret, quant_bits=quant_bits,
                             double_buffer=double_buffer)
               for st in program.stages]
    is_mlp = program.beta is not None
    readout = program.readout_state
    c_slow = program.stages[0].schedule.c_slow

    def forward(params: PyTree, u: jnp.ndarray) -> jnp.ndarray:
        u = jnp.asarray(u, jnp.float32)
        C_streams = u.shape[0] if c_slow > 1 else 1
        if c_slow > 1:  # [C, B, ...] -> [(C·B), ...]: batch-axis interleave
            u = fold_streams(u)
        C = jnp.asarray(params["C"], jnp.float32)
        sp = params["stages"]
        if is_mlp:
            x0 = {"x": u @ jnp.asarray(params["beta"], jnp.float32).T}
            finals, _ = runners[0](sp[0], x0, None)
            y = finals["x"] @ C.T
        else:
            ys = u
            finals = None
            for stage, run, p in zip(program.stages, runners, sp):
                B = ys.shape[0]
                x0 = {name: jnp.zeros((B, w), jnp.float32)
                      for name, w in stage.graph.states.items()}
                finals, ys = run(p, x0, ys)
            y = finals[readout] @ C.T
        if c_slow > 1:
            y = unfold_streams(y, C_streams)
        return y

    if mesh is None:
        return forward
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    if dp_n <= 1:
        return forward

    sm = jax.shard_map(forward, mesh=mesh, in_specs=(P(), P(dp)),
                       out_specs=P(dp), check_vma=False)

    def sharded_forward(params: PyTree, u: jnp.ndarray) -> jnp.ndarray:
        u = jnp.asarray(u, jnp.float32)
        n = u.shape[0]
        return sm(params, _pad_to(u, -(-n // dp_n) * dp_n, 0))[:n]

    return sharded_forward
