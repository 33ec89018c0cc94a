"""Open loop: 99th percentile of how late the generator submitted a request
after it was due (ms); the server's blocks run on the same thread."""

from bench.stats import pct


def read(run):
    if not run.open_loop:
        return None
    return pct([(r.submitted - r.due) * 1e3 for r in run.counted], 99)
