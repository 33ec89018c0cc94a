"""Share of its roofline reached by the held-expert grouped product (the expert_gmm Mosaic call) over the window's decode ticks and admitted prefills (%)."""


def read(run):
    """Least time per call: the larger of the expected held picks' FLOPs at
    peak and the held experts' weights at operand bytes plus the picks'
    activations at HBM bandwidth (``expert_kernel_cost`` of the
    configuration), once per MoE layer for each decode tick (tokens: the
    block's emitted tokens over K) and each admitted prefill."""
    if run.trace is None or not run.trace.program_runs.get("expert_kernel"):
        return None
    m, s, pk = run.cell.model, run.sizes, run.peaks
    least_s = lambda f, b: max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
    k = run.cell.config["server"]["block_k"]
    calls = m.expert_kernel_calls(s)
    least = sum(k * calls * least_s(*m.expert_kernel_cost(s, b.emitted / k))
                for b in run.blocks if b.emitted)
    least += sum(calls * least_s(*m.expert_kernel_cost(s, len(r.req.prompt)))
                 for r in run.admitted)
    return 100.0 * least / run.trace.program_s["expert_kernel"] if least else None
