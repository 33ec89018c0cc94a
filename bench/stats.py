"""Per-request numbers of a run, on the harness's clock, for metric readers."""

from __future__ import annotations

import numpy as np

from .harness import DRAIN_LIMIT_S, Run


def pct(values, q: float) -> float | None:
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def ttft_ms(run: Run) -> list[float]:
    """From when each counted request was due to its first token.  One that
    failed or never got a token counts as waiting until the harness stopped
    looking (window close plus the drain limit)."""
    stop = run.t_close + DRAIN_LIMIT_S
    out = []
    for r in run.counted:
        t = r.req.first_token_at
        ok = r.req.finish_reason in (None, "max_tokens", "eos")
        out.append(((t if t is not None and ok else stop) - r.due) * 1e3)
    return out


def tpot_ms(run: Run) -> list[float]:
    """Per counted request with two tokens or more: from its first token to
    its last one seen (at retirement, or at the end of the drain), over the
    tokens after the first."""
    out = []
    for r in run.counted:
        if r.req.first_token_at is not None and r.last_t is not None and r.last_n > 1:
            out.append((r.last_t - r.req.first_token_at) / (r.last_n - 1) * 1e3)
    return out
