"""95th percentile over requests of (last token - first token) / (tokens - 1) (ms)."""

from bench.stats import pct, tpot_ms


def read(run):
    return pct(tpot_ms(run), 95)
