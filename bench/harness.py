"""Run one benchmark cell once: set up, warm, measure a window, check, report.

Everything that belongs to one piece is found by its name:

* ``BENCHMARK.json`` (repository root): cells, configurations, metrics;
* ``bench/configs/<config>.json``: the configuration as run (sizes, the
  program's config and overrides, server settings);
  ``bench/configs/<config>.py``: weights from the seed, the plain
  reference, and the FLOP and byte counts;
* ``bench/traffic/<mix>.json``: one traffic mix, read by ``traffic.py``;
* ``bench/cells/<workload>.json``: what one cell fixes (its open-loop rate,
  the limits of its comparison);
* ``bench/metrics/<metric>.py``: ``read(run)`` for one metric, returning
  ``None`` where the run has nothing to read.

The window drives ``DecodeServer.submit`` and ``DecodeServer.step_block``
from one thread: the generator submits what is due, the server runs one
block (admission prefills, then K decode ticks), and so on.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any

import numpy as np

from . import traffic as traffic_lib

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
DRAIN_LIMIT_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OK_REASONS = ("max_tokens", "eos")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def clock() -> float:
    return time.perf_counter()


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def host_cpu() -> tuple[float, float]:
    """This process's CPU seconds, and the CPU seconds the hypervisor took
    from the host's cores (``steal``, all cores summed, Linux ``/proc``)."""
    with open("/proc/stat") as f:
        steal = int(f.readline().split()[8])
    return time.process_time(), steal / os.sysconf("SC_CLK_TCK")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    model: Any              # bench/configs/<config>.py
    mix: dict               # bench/traffic/<mix>.json
    data: dict              # bench/cells/<workload>.json
    metrics: list[dict]     # BENCHMARK.json entries reported with --trace 0
    layer_metrics: list[dict]   # ... with --trace 1


def _reported(entries: list[dict], workload: str) -> list[dict]:
    return [m for m in entries if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cpath = root / configs[w["config"]]["file"]
    return Cell(
        name=workload, chips=w["chips"],
        config=read_json(cpath), model=load_module(cpath.with_suffix(".py")),
        mix=read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        data=read_json(root / "bench" / "cells" / f"{workload}.json"),
        metrics=_reported(bench["end_to_end"], workload),
        layer_metrics=_reported(bench["per_layer"], workload))


# ---------------------------------------------------------------------------
# JAX set-up
# ---------------------------------------------------------------------------

def start_jax(chips: int, require_tpu: bool = True):
    """Import JAX with the compile cache in the checkout; the devices."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def seed_key(seed: int):
    import jax

    s = abs(int(seed))
    k = jax.random.fold_in(jax.random.PRNGKey(0), (s >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, s & 0xFFFFFFFF)


def make_weights(cell: Cell, seed: int):
    """The configuration's weights, on the device, in one jitted call."""
    import jax

    sizes = cell.config["sizes"]
    return jax.block_until_ready(
        jax.jit(functools.partial(cell.model.init_weights, sizes))(seed_key(seed)))


def model_config(cell: Cell):
    import dataclasses as dc

    from repro.configs import get_config

    prog = cell.config["program"]
    return dc.replace(get_config(prog["arch"]), **prog.get("overrides", {}))


def check_layout(cfg, weights) -> None:
    """The weights must have exactly the program's parameter layout."""
    import jax

    from repro.models import lm

    want = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("bench weights do not match the program's parameter layout")


# ---------------------------------------------------------------------------
# the driving loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the harness sees it."""
    req: Any                    # the server's Request
    due: float
    submitted: float = 0.0
    last_t: float | None = None  # time of the last block that gave it tokens
    last_n: int = 0
    client: int | None = None


@dataclasses.dataclass
class Block:
    t_start: float
    emitted: int                # decode tokens the block gave


class Driver:
    """Submits what is due and steps the server, one thread, one clock."""

    def __init__(self, server, cell: Cell, seed: int, annotate: bool):
        self.server = server
        self.mix = cell.mix
        self.recs: list[Rec] = []
        self.by_uid: dict[int, Rec] = {}
        self.blocks: list[Block] = []
        self._done_seen = 0
        self._uid = 0
        self._annotate = annotate
        self.closed_on = True       # closed loop: a reply sends the next request
        self.vocab = cell.config["sizes"]["vocab_size"]
        if self.mix["loop"] == "closed":
            self.source = traffic_lib.ClosedSource(self.mix, self.vocab, seed)
            self.first = list(traffic_lib.first_outputs(self.mix, seed))

    def _span(self, name: str):
        import contextlib

        if not self._annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def submit(self, item: traffic_lib.Item, due: float, client=None) -> Rec:
        from repro.runtime import Request

        req = Request(uid=self._uid, prompt=item.prompt,
                      max_new_tokens=item.max_new_tokens)
        self._uid += 1
        rec = Rec(req=req, due=due, client=client)
        with self._span("bench.submit"):
            rec.submitted = clock()
            self.server.submit(req)
        self.recs.append(rec)
        self.by_uid[req.uid] = rec
        return rec

    def busy(self) -> bool:
        return bool(self.server.live.any() or len(self.server.scheduler))

    def block(self) -> None:
        srv = self.server
        before = srv.decoded_tokens
        t = clock()
        with self._span("bench.step_block"):
            srv.step_block()
        now = clock()
        self.blocks.append(Block(t, srv.decoded_tokens - before))
        for r in srv.slot_req:
            if r is not None:
                rec = self.by_uid[r.uid]
                rec.last_t, rec.last_n = now, len(r.out_tokens)
        done = srv.completed[self._done_seen:]
        self._done_seen = len(srv.completed)
        for r in done:
            rec = self.by_uid[r.uid]
            if r.out_tokens:
                rec.last_t, rec.last_n = r.done_at, len(r.out_tokens)
            if rec.client is not None and self.closed_on:
                self.submit(self.source.next(), now, rec.client)

    # -- phases --------------------------------------------------------------

    def start_closed(self) -> None:
        for c in range(self.mix["clients"]):
            item = self.source.next()
            item.max_new_tokens = int(min(item.max_new_tokens, self.first[c]))
            self.submit(item, clock(), client=c)

    def run_open(self, items: list[traffic_lib.Item], t_start: float,
                 t_end: float) -> None:
        i = 0
        while True:
            now = clock()
            if now >= t_end:
                return
            while i < len(items) and t_start + items[i].offset_s <= now:
                self.submit(items[i], t_start + items[i].offset_s)
                i += 1
            if self.busy():
                self.block()
            else:
                nxt = t_start + items[i].offset_s if i < len(items) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - clock()))

    def run_closed(self, t_end: float) -> None:
        while clock() < t_end:
            self.block()

    def drain(self, counted: list[Rec], limit_s: float) -> None:
        """Without new arrivals, step until every counted request has its
        first token (or has retired), or ``limit_s`` passes."""
        self.closed_on = False
        t_stop = clock() + limit_s
        while clock() < t_stop and self.busy() and any(
                r.req.first_token_at is None and r.req.done_at is None
                for r in counted):
            self.block()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader gets."""
    cell: Cell
    seed: int
    t0: float
    t_close: float
    counted: list[Rec]
    blocks: list[Block]
    admitted: list[Rec]          # dispatched inside the window
    tokens: int                  # tokens emitted inside the window
    compiles: int
    setup: dict
    peaks: dict
    trace: Any = None            # devtrace.Reduction of the traced window

    @property
    def window_s(self) -> float:
        return self.t_close - self.t0

    @property
    def open_loop(self) -> bool:
        return self.cell.mix["loop"] == "open"

    @property
    def sizes(self) -> dict:
        return self.cell.config["sizes"]


def _tokens_out(recs: list[Rec]) -> int:
    return sum(len(r.req.out_tokens) for r in recs)


def read_metric(entry: dict, run: Run):
    mod = load_module(BENCH / "metrics" / f"{entry['name']}.py")
    return mod.read(run)


def peaks_for(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, control: bool = False, log=print) -> dict:
    """Set up, warm, measure, check; returns the result object.  With
    ``control`` the bfloat16 control is judged on the same sample as well
    (``out["control"]``: its verdict, numbers and limits), for setting the
    limits; benchmark runs never do."""
    age0 = process_age_s()
    t_proc = clock() - age0
    split = {}
    t = clock()
    devs = start_jax(cell.chips, require_tpu)
    import jax

    from repro.runtime import DecodeServer

    peaks = peaks_for(devs[0].device_kind) if require_tpu else {}
    split["tpu_start_s"] = clock() - t_proc
    t = clock()
    cfg = model_config(cell)
    weights = make_weights(cell, seed)
    check_layout(cfg, weights)
    split["weights_s"] = clock() - t

    srv_cfg = cell.config["server"]
    mix = cell.mix
    server = DecodeServer(cfg, weights, num_slots=mix["slots"],
                          max_seq=srv_cfg["max_seq"], eos_id=srv_cfg["eos_id"],
                          block_k=srv_cfg["block_k"], persistent=True,
                          prefill_chunk=srv_cfg["prefill_chunk"],
                          prefix_cache_bytes=srv_cfg["prefix_cache_bytes"])
    drv = Driver(server, cell, seed, annotate=trace)

    # compiles or cache loads: one prefill per grid length and the decode
    # block at the cell's slot count, through the server's own programs
    t = clock()
    rng = traffic_lib.rng_for(seed, 3)
    for L in mix["prompt"]["grid"]:
        drv.submit(traffic_lib.Item(rng.integers(0, drv.vocab, L).tolist(), 2), clock())
    while drv.busy():
        drv.block()
    split["compile_s"] = clock() - t

    # warm traffic, then the window follows it without a break
    t = clock()
    warm = mix["warm_seconds"]
    if mix["loop"] == "open":
        rate = cell.data["rate_rps"]
        warm_items = traffic_lib.phase_items(mix, drv.vocab, seed, 1,
                                             traffic_lib.open_count(rate, warm), warm, rate)
        win_items = traffic_lib.phase_items(mix, drv.vocab, seed, 2,
                                            traffic_lib.open_count(rate, seconds), seconds, rate)
        drv.run_open(warm_items, t, t + warm)
    else:
        drv.start_closed()
        drv.run_closed(t + warm)
    split["warm_traffic_s"] = clock() - t

    compiles = [0]
    counting = [False]
    gc_pause = [0.0, 0, None]      # seconds, collections, start of the current one

    def on_event(event, _secs, **_kw):
        if counting[0] and event == COMPILE_EVENT:
            compiles[0] += 1

    def on_gc(phase, _info):
        if not counting[0]:
            return
        if phase == "start":
            gc_pause[2] = clock()
        elif gc_pause[2] is not None:
            gc_pause[0] += clock() - gc_pause[2]
            gc_pause[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    # what set-up made is never garbage: the collector scans only what the
    # window makes (unfrozen again before the program's state is freed)
    gc.freeze()
    gc.callbacks.append(on_gc)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    n_before = len(drv.recs)
    out_before = _tokens_out(drv.recs)
    counting[0] = True
    cpu0 = host_cpu()
    t0 = clock()
    setup_s = t0 - t_proc
    with drv._span("bench.window"):
        if mix["loop"] == "open":
            drv.run_open(win_items, t0, t0 + seconds)
        else:
            drv.run_closed(t0 + seconds)
        t_close = clock()
    cpu1 = host_cpu()
    counting[0] = False
    gc.callbacks.remove(on_gc)
    gc.unfreeze()
    tokens = _tokens_out(drv.recs) - out_before
    window_blocks = [b for b in drv.blocks if b.t_start >= t0]
    if mix["loop"] == "open":
        counted = [r for r in drv.recs[n_before:] if r.due < t0 + seconds]
    else:
        counted = [r for r in drv.recs[n_before:] if r.submitted < t_close]
    admitted = [r for r in drv.recs if r.req.dispatched_at is not None
                and t0 <= r.req.dispatched_at < t_close]
    jax.monitoring.unregister_event_duration_listener(on_event)
    if trace:
        jax.profiler.stop_trace()
    drv.drain(counted, DRAIN_LIMIT_S)
    mem_peak = int((devs[0].memory_stats() or {}).get("peak_bytes_in_use", 0))

    run = Run(cell=cell, seed=seed, t0=t0, t_close=t_close, counted=counted,
              blocks=window_blocks, admitted=admitted, tokens=tokens,
              compiles=compiles[0], setup=dict(split, setup_s=setup_s),
              peaks=peaks)
    if trace:
        from . import devtrace

        run.trace = devtrace.reduce_dir(TRACE_DIR, cell.config["trace_programs"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    entries = cell.layer_metrics if trace else cell.metrics
    metrics = {}
    for m in entries:
        v = read_metric(m, run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    log(f"[setup] {json.dumps({k: round(v, 3) for k, v in run.setup.items()})}")

    # free the program's state before the reference runs
    finished = [r.req for r in counted if r.req.finish_reason in OK_REASONS
                and r.req.done_at is not None]
    failed = sum(1 for r in counted if r.req.first_token_at is None
                 or (r.req.finish_reason or "max_tokens") not in OK_REASONS)
    drv_done = [r for r in server.completed if r.done_at is not None]
    del server, drv
    for leaf in jax.tree.leaves(weights):
        leaf.delete()
    del weights
    gc.collect()

    checks, correct, stats, ctrl = correctness(cell, seed, finished, failed, control)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": len(counted),
           "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["load"] = {
        "offered_rps": len(counted) / run.window_s,
        "completed_rps": sum(1 for r in drv_done if t0 <= r.done_at < t_close) / run.window_s,
        "first_token_in_window_share": sum(
            1 for r in counted if r.req.first_token_at is not None
            and r.req.first_token_at < t_close) / max(1, len(counted)),
        "gc_pause_s": gc_pause[0],
        "gc_collections": gc_pause[1],
        # where a run reads slow: the longest time between block starts, and
        # whether the process or the host lost the CPU meanwhile
        "longest_block_gap_s": float(np.max(np.diff(
            [t0] + [b.t_start for b in window_blocks] + [t_close]))),
        "process_cpu_s": cpu1[0] - cpu0[0],
        "host_steal_s": cpu1[1] - cpu0[1],
    }
    out["compare"] = stats
    if control:
        out["control"] = ctrl
    out["checks"] = checks
    return out


def correctness(cell: Cell, seed: int, finished: list, failed: int,
                control: bool = False) -> tuple[dict, bool, dict, dict | None]:
    """The compared numbers, each with its limit, the verdict, and every gap
    number of the sample (limited or not); with
    ``control``, the bfloat16 control's verdict and numbers on the same
    sample, judged by the same limits."""
    import jax

    from . import check

    lim = cell.data["limits"]
    sample = check.sample_requests(finished, seed, lim["sample_requests"])
    gaps = np.zeros(0)
    ctrl = None
    if sample:
        sizes = cell.config["sizes"]
        w = jax.jit(functools.partial(cell.model.init_weights, sizes))(seed_key(seed))
        n = lim["sample_requests"]
        gaps = check.served_gaps(cell.model, sizes, w, sample, n_rows=n)
        if control:
            lo = check.served_gaps(cell.model, sizes, w, sample, n_rows=n, control=True)
            c_checks, c_ok = check.judge(check.gap_stats(lo), int(lo.size), failed, lim)
            ctrl = {"correct": c_ok, "stats": check.gap_stats(lo), "checks": c_checks,
                    "requests": len(sample)}
        del w
    stats = check.gap_stats(gaps)
    checks, correct = check.judge(stats, int(gaps.size), failed, lim)
    return checks, correct, stats, ctrl


def format_checks(checks: dict) -> list[str]:
    lines = []
    for name, c in checks.items():
        op = "<=" if c.get("at_most", True) else ">="
        lines.append(f"check {name}: {c['value']} (limit {op} {c['limit']})")
    return lines


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = functools.partial(print, file=sys.stderr, flush=True)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace), log=log)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for line in format_checks(out["checks"]):
        log(line)
    # the checks go last in the result line, as plain names and numbers
    checks = {k: {"value": c["value"], "limit": c["limit"]} for k, c in out["checks"].items()}
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0
