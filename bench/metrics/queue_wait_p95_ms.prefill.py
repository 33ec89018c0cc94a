"""Prefill-heavy cells: 95th percentile from due to dispatch
(``Request.dispatched_at``: popped from the scheduler into a slot) of the
requests due in the window (ms)."""

from bench.stats import pct


def read(run):
    return pct([(r.req.dispatched_at - r.due) * 1e3 for r in run.counted
                if r.req.dispatched_at is not None], 95)
