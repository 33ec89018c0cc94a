"""Device idle time attributed to the program's own host spans.

With tracing on (``repro.obs.Observability(trace=True)``) every span of the
program also lands in the profiler trace as a host event named
``repro.<span>``, on the device trace's clock.  Each idle interval of the
first device inside ``bench.window`` is cut at every host-span boundary, and
each piece goes to every ``repro.*`` span open over it, at any depth; a piece
inside no program span goes to the harness span around it
(``bench.step_block``, ``bench.submit``), else to ``no request in flight``.

``devtrace`` keeps only the harness's host spans and the harness builds its
server untraced, so this reduction reads its own events
(``bench/span_idle.py`` runs a cell that way); busy time and the idle gaps
are those ``devtrace`` computes, from the same device events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import Counter, defaultdict

from . import devtrace
from .devtrace import Event

PREFIX = "repro."
NO_REQUEST = "no request in flight"
STEP_BLOCK = "bench.step_block"

# what each span metric counts: idle under any span of the first list and
# none of the second
METRICS = {
    "idle_admit_pct": (("repro.admit",), ()),
    "idle_dispatch_pct": (("repro.block_prep", "repro.decode_block"), ("repro.device_sync",)),
    "idle_post_block_pct": (("repro.post_block",), ()),
}


def _host_span(name: str) -> bool:
    return name.startswith(PREFIX) or name in devtrace.HOST_SPANS


def load_events(path: str) -> list[Event]:
    """The events ``devtrace.load_events`` keeps, and the ``repro.*`` host
    spans, in one pass over the trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (devtrace.OPS_LINE, devtrace.MODULES_LINE):
                continue
            for ev in line.events:
                if not device and ev.name != devtrace.WINDOW_SPAN and not _host_span(ev.name):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return list(dict.fromkeys(out))


@dataclasses.dataclass
class SpanIdle:
    window_s: float
    pieces: dict[frozenset, float]      # idle seconds, by the host spans open over them

    @property
    def traced(self) -> bool:
        """Whether the trace holds any program span."""
        return any(n.startswith(PREFIX) for names in self.pieces for n in names)

    def seconds(self, inside, outside=()) -> float:
        return sum(t for names, t in self.pieces.items()
                   if names.intersection(inside) and not names.intersection(outside))

    def pct(self, inside, outside=()) -> float | None:
        """100 × idle seconds under ``inside`` and not ``outside`` ÷ the
        window; ``None`` where the program wrote no spans."""
        if not self.traced:
            return None
        return 100.0 * self.seconds(inside, outside) / self.window_s

    def metrics(self) -> dict[str, float | None]:
        return {name: self.pct(*spans) for name, spans in METRICS.items()}

    def by_span(self, top: int = 10) -> list[tuple[str, float]]:
        """Idle seconds per span, the longest first: every program span over
        a piece counts it; a piece under none counts for the harness span
        around it, else for ``no request in flight``."""
        out: dict[str, float] = defaultdict(float)
        for names, t in self.pieces.items():
            prog = sorted(n for n in names if n.startswith(PREFIX))
            for n in prog or sorted(names) or [NO_REQUEST]:
                out[n] += t
        return sorted(out.items(), key=lambda kv: -kv[1])[:top]

    def program_share(self) -> float | None:
        """Share of the idle inside ``bench.step_block`` that program spans
        hold."""
        inner = self.seconds((STEP_BLOCK,))
        if not self.traced or inner == 0:
            return None
        prog = sum(t for names, t in self.pieces.items()
                   if STEP_BLOCK in names and any(n.startswith(PREFIX) for n in names))
        return prog / inner


def _idle(events: list[Event]) -> tuple[float, float, list[tuple[float, float]]]:
    """The window, and the first device's idle intervals inside it."""
    win = [e for e in events if e.name == devtrace.WINDOW_SPAN
           and not e.plane.startswith("/device:")]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0].start_ns, win[0].end_ns
    devices = sorted({e.plane for e in events if e.plane.startswith("/device:")
                      and e.line == devtrace.OPS_LINE})
    busy = devtrace.union_ns(
        c for e in events if devices and e.plane == devices[0] and e.line == devtrace.OPS_LINE
        for c in [devtrace._clip(e.start_ns, e.end_ns, lo, hi)] if c)
    idle, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    return lo, hi, idle


def reduce_events(events: list[Event]) -> SpanIdle:
    lo, hi, idle = _idle(events)
    marks = sorted([(e.start_ns, 1, e.name) for e in events
                    if not e.plane.startswith("/device:") and _host_span(e.name)]
                   + [(e.end_ns, -1, e.name) for e in events
                      if not e.plane.startswith("/device:") and _host_span(e.name)])
    open_spans: Counter = Counter()
    pieces: dict[frozenset, float] = defaultdict(float)
    i = 0

    def advance(t: float) -> None:
        nonlocal i
        while i < len(marks) and marks[i][0] <= t:
            open_spans[marks[i][2]] += marks[i][1]
            i += 1

    def credit(ns: float) -> None:
        if ns > 0:
            pieces[frozenset(n for n, c in open_spans.items() if c > 0)] += ns * 1e-9

    for s, e in idle:
        advance(s)
        t = s
        while i < len(marks) and marks[i][0] < e:
            credit(marks[i][0] - t)
            t = marks[i][0]
            advance(t)
        credit(e - t)
    return SpanIdle(window_s=(hi - lo) * 1e-9, pieces=dict(pieces))


def reduce_dir(trace_dir) -> SpanIdle:
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(files)}")
    return reduce_events(load_events(files[0]))
