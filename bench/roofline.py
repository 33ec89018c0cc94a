"""Required work over device time, from a run's trace and the configuration's
counts (``bench/configs/<config>.py``).  Each function returns a percentage,
or ``None`` where the traced window holds none of that work."""

from __future__ import annotations

from .harness import Run


def _least_s(run: Run, flops: float, nbytes: float) -> float:
    """Roofline: the larger of compute at peak and bytes at HBM bandwidth."""
    return max(flops / run.peaks["flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])


def _device_s(run: Run, role: str) -> float | None:
    if run.trace is None or not run.trace.program_runs.get(role):
        return None
    return run.trace.program_s[role]


def prefill_mfu(run: Run) -> float | None:
    t = _device_s(run, "prefill")
    if t is None or not run.admitted:
        return None
    m, s = run.cell.model, run.sizes
    flops = sum(m.prefill_flops(s, len(r.req.prompt)) for r in run.admitted)
    return 100.0 * flops / (t * run.peaks["flops_per_s"])


def stage_kernel(run: Run) -> float | None:
    """Bound per call by the larger of the gate contraction's FLOPs and the
    gate ROM's bytes: one call per layer per prefill."""
    t = _device_s(run, "stage_kernel")
    if t is None or not run.admitted:
        return None
    m, s = run.cell.model, run.sizes
    least = sum(m.stage_kernel_calls(s) * _least_s(run, *m.stage_kernel_cost(s, len(r.req.prompt)))
                for r in run.admitted)
    return 100.0 * least / t


def decode_block(run: Run) -> float | None:
    """Per tick: the weights once plus the live slots' state read and
    written (bound by HBM at these sizes), against the FLOPs of the tokens."""
    t = _device_s(run, "decode")
    if t is None or not run.blocks:
        return None
    m, s = run.cell.model, run.sizes
    k = run.cell.config["server"]["block_k"]
    least = 0.0
    for b in run.blocks:
        if b.emitted:
            nbytes = k * m.decode_tick_bytes(s, 0) + b.emitted * 2.0 * m.state_bytes_per_slot(s)
            least += _least_s(run, b.emitted * m.decode_flops_per_token(s), nbytes)
    return 100.0 * least / t if least else None


def decode_mfu(run: Run) -> float | None:
    t = _device_s(run, "decode")
    if t is None or not run.blocks:
        return None
    m, s = run.cell.model, run.sizes
    flops = sum(b.emitted for b in run.blocks) * m.decode_flops_per_token(s)
    return 100.0 * flops / (t * run.peaks["flops_per_s"]) if flops else None


def idle(run: Run) -> float | None:
    return None if run.trace is None else run.trace.idle_pct
