"""Median time to first token of the requests due in the window (ms)."""

from bench.stats import pct, ttft_ms


def read(run):
    return pct(ttft_ms(run), 50)
