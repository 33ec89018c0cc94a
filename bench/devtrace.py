"""From a profiler trace to device intervals: busy time, program and kernel
time, idle gaps, all inside the traced window.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line
holds one event per operation run (a fusion, a custom call such as a Mosaic
kernel) and the ``XLA Modules`` line one event per program run.  The host
plane holds the harness's ``TraceAnnotation`` spans on the same clock:
``bench.window`` bounds the window, ``bench.submit`` and ``bench.step_block``
label what the host did during a device gap.

Which programs and kernels a metric reads is named per configuration, as
regular expressions over event names (``trace_programs`` in
``bench/configs/<config>.json``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.step_block", "bench.submit")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and ev.name not in (WINDOW_SPAN,) + HOST_SPANS:
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    # a plane can be listed twice in one trace: keep each event once
    return list(dict.fromkeys(out))


def union_ns(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # busy union, averaged over devices
    program_s: dict[str, float]         # per role in trace_programs
    program_runs: dict[str, int]
    top_ops: list[tuple[str, float]]
    gaps: list[tuple[str, float]]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops],
                "idle_gaps": [[n, s] for n, s in self.gaps]}


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``;
    ``jit_block(8034...)`` -> ``jit_block``."""
    return op.split(" = ", 1)[0].split("(", 1)[0]


def self_times(evs: list[Event]) -> dict[str, float]:
    """Seconds per op name, each event less the events nested in it (a
    ``while`` op holds its body's ops on the same line)."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [end_ns, name, self_ns]
    for e in sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            end, name, own = stack.pop()
            out[name] += own * 1e-9
        if stack:
            stack[-1][2] -= min(e.end_ns, stack[-1][0]) - e.start_ns
        stack.append([e.end_ns, e.name, e.dur_ns])
    for end, name, own in stack:
        out[name] += own * 1e-9
    return out


def reduce_events(events: list[Event], programs: dict[str, str],
                  top: int = 10) -> Reduction:
    """Reduce one traced window.  ``programs`` maps a role (``prefill``,
    ``decode``, ``stage_kernel``, ...) to a regular expression over event
    names; a role is read on the modules line, or on the ops line where its
    key ends in ``kernel``."""
    win = [e for e in events if e.name == WINDOW_SPAN and not e.plane.startswith("/device:")]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0].start_ns, win[0].end_ns

    def clipped(plane, line):
        for e in events:
            if e.plane == plane and e.line == line:
                c = _clip(e.start_ns, e.end_ns, lo, hi)
                if c:
                    yield e, c

    devices = sorted({e.plane for e in events if e.plane.startswith("/device:")
                      and e.line == OPS_LINE})
    busy, op_time = {}, defaultdict(float)
    for d in devices:
        busy[d] = union_ns(c for _, c in clipped(d, OPS_LINE))
        mods = sorted((c[0], c[1], short_name(e.name)) for e, c in clipped(d, MODULES_LINE))
        ops = [Event(d, OPS_LINE, _in_module(mods, c) + "/" + short_name(e.name), c[0], c[1] - c[0])
               for e, c in clipped(d, OPS_LINE)]
        for name, t in self_times(ops).items():
            op_time[name] += t / len(devices)
    busy_s = sum(sum(e - s for s, e in iv) for iv in busy.values()) * 1e-9 / max(1, len(devices))

    program_s, runs = {}, {}
    for role, pattern in programs.items():
        rx = re.compile(pattern)
        line = OPS_LINE if role.endswith("kernel") else MODULES_LINE
        hits = [c for d in devices for e, c in clipped(d, line) if rx.search(e.name)]
        program_s[role] = sum(e - s for s, e in hits) * 1e-9 / max(1, len(devices))
        runs[role] = len(hits)

    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                     program_s=program_s, program_runs=runs,
                     top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
                     gaps=_gaps(events, busy[devices[0]] if devices else [], lo, hi, top))


def _in_module(mods, c) -> str:
    """The program run (``mods``: sorted, non-overlapping) holding ``c``."""
    i = bisect.bisect_right(mods, (c[0], math.inf)) - 1
    return mods[i][2] if i >= 0 and c[0] < mods[i][1] else "(no module)"


def _gaps(events, busy, lo, hi, top) -> list[tuple[str, float]]:
    """Idle time by what the host was doing (the harness span around the
    gap's midpoint): first the total per activity, then the longest gaps."""
    spans = sorted((e.start_ns, e.end_ns, e.name) for e in events if e.name in HOST_SPANS)
    gaps, prev = [], lo
    for s, e in list(busy) + [(hi, hi)]:
        if s > prev:
            # host spans do not overlap: the last one to start before the
            # midpoint holds it, if it has not ended
            mid = 0.5 * (prev + s)
            i = bisect.bisect_right(spans, (mid, math.inf, "")) - 1
            inner = spans[i][2] if i >= 0 and mid <= spans[i][1] else "no request in flight"
            gaps.append(("host: " + inner, (s - prev) * 1e-9))
        prev = max(prev, e)
    totals: dict[str, list] = defaultdict(lambda: [0.0, 0])
    for label, t in gaps:
        totals[label][0] += t
        totals[label][1] += 1
    out = [(f"total, {label}, {n} gaps", t)
           for label, (t, n) in sorted(totals.items(), key=lambda kv: -kv[1][0])]
    out += sorted(gaps, key=lambda g: -g[1])
    return out[:top]


def reduce_dir(trace_dir, programs: dict[str, str]) -> Reduction:
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(files)}")
    return reduce_events(load_events(files[0]), programs)
