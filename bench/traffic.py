"""The one traffic generator: turns a mix's data file and a seed into requests.

A mix (``bench/traffic/<mix>.json``) gives distributions, not requests:

* ``loop``: ``"open"`` (arrivals on a schedule, ``arrivals: "poisson"`` at
  the cell's ``rate_rps``) or ``"closed"`` (``clients`` callers, each sending
  its next request when its reply arrives);
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma"}`` or
  ``{"dist": "uniform"}``, clipped to ``[min, max]``; a prompt length is then
  rounded up to the next entry of ``grid`` (the lengths the cell warms);
* ``slots``: the server's slot count; ``warm_seconds``: traffic run before the
  window so that it starts in steady state;
* ``source``: the public trace summary the lengths follow, or that none does.

Every seed gets the same multiset of lengths and inter-arrival gaps: they are
the distribution's quantiles at evenly spaced probabilities, and the seed
only permutes them and draws the token ids.  So two seeds differ in order,
not in the amount of work, and run-to-run spread is not seed-to-seed work.
Open-loop gaps are exponential quantiles in a random order, so arrivals are
as bursty from one gap to the next as a Poisson process; only the count in
the window is fixed (the gaps are scaled by the small factor that makes
``n`` of them span it).
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any integer seed)."""
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), stream]))


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the probabilities (i + 1/2) / n of ``spec``."""
    p = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + p * (spec["max"] - spec["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)
    grid = spec.get("grid")
    if grid:
        g = np.asarray(sorted(grid))
        v = g[np.searchsorted(g, v)]
    return v


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""
    prompt: list[int]
    max_new_tokens: int
    offset_s: float = 0.0       # open loop: due time after the phase start


def phase_items(mix: dict, vocab: int, seed: int, stream: int, n: int,
                seconds: float = 0.0, rate: float = 0.0) -> list[Item]:
    """``n`` requests of one phase (warm-up or window) of a mix.

    Open loop: the gaps are exponential quantiles with mean ``1/rate``,
    scaled so that the ``n`` arrivals span ``seconds`` exactly, then shuffled.
    """
    rng = rng_for(seed, stream)
    plens = rng.permutation(quantiles(mix["prompt"], n))
    outs = rng.permutation(quantiles(mix["output"], n))
    offsets = np.zeros(n)
    if mix["loop"] == "open" and n:
        p = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-p) / rate)
        offsets = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    return [Item(prompt=rng.integers(0, vocab, int(L)).tolist(),
                 max_new_tokens=int(o), offset_s=float(t))
            for L, o, t in zip(plens, outs, offsets)]


def open_count(rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


class ClosedSource:
    """Closed loop: the requests the clients send, in order.  They come in
    batches of ``BATCH`` (stream ``100 + i``), each batch the same multiset
    of lengths for every seed, so a fast server only draws more batches."""

    BATCH = 256

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self._buf: list[Item] = []
        self._batches = 0

    def next(self) -> Item:
        if not self._buf:
            self._buf = phase_items(self.mix, self.vocab, self.seed,
                                    100 + self._batches, self.BATCH)[::-1]
            self._batches += 1
        return self._buf.pop()


def first_outputs(mix: dict, seed: int) -> np.ndarray:
    """Closed loop: each client's first request is a residual one, a share
    ``(i + 1/2) / clients`` of a drawn length, so that the clients do not
    all finish together; the shares are permuted over the clients."""
    rng = rng_for(seed, 7)
    c = mix["clients"]
    full = rng.permutation(quantiles(mix["output"], c))
    share = rng.permutation((np.arange(c) + 0.5) / c)
    return np.maximum(2, np.rint(full * share)).astype(np.int64)


__all__ = ["Item", "phase_items", "quantiles", "rng_for", "open_count",
           "ClosedSource", "first_outputs"]
