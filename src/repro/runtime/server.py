"""Batched decode server with slot-based continuous batching.

The serving state-space system made operational: B cache *slots* are the
state registers; each decode tick applies f once for all live slots
(per-slot positions — the C-slow interleave of independent streams through
one datapath).  Requests claim free slots, retire on EOS/max_tokens, and new
requests are admitted between ticks without recompiling.

Two decode drivers share the slot machinery:

* ``step()`` — the legacy per-token tick: one ``decode_step`` dispatch, one
  host↔device sync per generated token (logits come back to the host, the
  host samples in a Python loop).
* ``step_block()`` — the **persistent** driver (the paper's unroll knob
  applied to serving): a jitted ``lax.scan`` over ``block_k`` decode steps
  that samples *on device* (batched argmax / ``jax.random.categorical`` with
  per-slot temperature), tracks per-slot live masks and EOS / max-token /
  out-of-cache stopping on device, and returns the K×B token block, its
  flags and the updated carries packed into one array.  One upload and one
  fetch per K tokens instead of a sync per token — the hot path is
  dispatch-bound, not sync-bound.  The cache carry layout is
  exactly the ``splice_cache`` layout, so admission between blocks is
  unchanged.

Prefill is the paper's resumable iteration, and the production levers fall
out of that:

* **chunked prefill** (``prefill_chunk=N``) — a prompt is consumed N tokens
  per tick through ``lm.prefill_chunk`` (the same state update as decode,
  batched over a chunk), interleaved with decode ticks; a long prompt never
  head-of-line-blocks live slots, and every tick's device work is bounded by
  one chunk + one decode dispatch.
* **radix prefix cache** (``prefix_cache_bytes``) — chunk-boundary states are
  checkpointed into a :class:`~repro.runtime.prefix_cache.PrefixCache`;
  admissions sharing a stored prefix splice the checkpoint instead of
  recomputing shared prompt FLOPs (a full hit recomputes zero prompt steps).
* **scheduler** — admission control, priority classes, and fairness aging
  live in :class:`~repro.runtime.scheduler.Scheduler`, which replaces the
  FIFO deque.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_lib
from repro.kernels.mla_decode import ops as mla_ops
from repro.models import lm
from repro.models.config import ModelConfig

from . import faults as faults_lib
from .faults import TransientFault, Watchdog
from .prefix_cache import PrefixCache
from .scheduler import REJECT_DUPLICATE_UID, Scheduler, SchedulerConfig
from .shard_plan import ShardPlan

PyTree = Any

DEFAULT_BLOCK_K = 8

_SEQ_LEAVES = ("k", "v", "c_kv", "k_rope")


def splice_cache(caches: PyTree, prefill_caches: PyTree, b,
                 max_seq: int | None = None) -> PyTree:
    """Insert a B=1 prefill cache into batch slot ``b`` of the server cache.
    ``b`` may be traced: the server runs this as one jitted program that
    takes ``caches`` donated, so a splice updates the cache in place.

    Handles: full-length KV ([G,1,L,..] → [G,B,S_max,..] left-aligned), MLA
    latents, sliding-window ring buffers (last W positions placed at
    slot = pos mod W), and recurrent states — both SSM ``h``/``conv`` and
    LSTM/GRU ``(h, c)`` carries ([G,1,..] → batch row b): a recurrent carry
    has no sequence axis, so admission is a pure batch-row write and new
    requests never disturb other slots' streams.

    The ``p mod W`` wrap applies ONLY to sliding-window ring buffers, i.e.
    destinations shorter than ``max_seq``.  An over-length source against a
    *full-attention* destination (L > S_dst == max_seq) raises — admission
    must reject or truncate such prompts, because wrapping a full cache
    would silently corrupt the slot (early positions overwritten by late
    ones while the causal mask still exposes every position).
    """

    def one(path, dst, src):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if src is None or (hasattr(src, "ndim") and src.ndim == 0):
            return dst
        if src.ndim >= 3 and dst.ndim == src.ndim and src.shape[2] != dst.shape[2] \
                and name.split("/")[-1] in _SEQ_LEAVES:
            # sequence-bearing cache: [G, 1, L, ...] -> [G, B, S_dst, ...]
            L, S_dst = src.shape[2], dst.shape[2]
            if L <= S_dst:
                return dst.at[:, b, :L].set(src[:, 0].astype(dst.dtype))
            if max_seq is None or S_dst >= max_seq:
                raise ValueError(
                    f"splice_cache: prompt of length {L} overflows the "
                    f"full-attention cache leaf '{name}' (S_max={S_dst}); "
                    "admission must reject or truncate — only sliding-window "
                    "ring buffers may wrap."
                )
            # ring buffer (sliding window): keep last S_dst, map p -> p mod W
            W = S_dst
            tail = src[:, 0, L - W:]                     # positions L-W .. L-1
            pos = np.arange(L - W, L)
            slots = pos % W
            return dst.at[:, b, slots].set(tail.astype(dst.dtype))
        if src.ndim == dst.ndim and src.shape[1] == 1:
            # batch-row state (SSM h/conv, equal-length KV)
            if src.shape[2:] == dst.shape[2:]:
                return dst.at[:, b].set(src[:, 0].astype(dst.dtype))
        return dst

    return jax.tree_util.tree_map_with_path(one, caches, prefill_caches)


def fill_slot(caches: PyTree, b, value, *, num_slots: int,
              floating_only: bool) -> PyTree:
    """Set batch row ``b`` of every cache leaf that has the slot axis (axis
    1, of length ``num_slots``) to ``value``; ``floating_only`` leaves
    integer leaves alone."""

    def one(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and leaf.shape[1] == num_slots and (
                not floating_only or jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf.at[:, b].set(jnp.asarray(value).astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map(one, caches)


def mla_layers(caches: PyTree) -> int:
    """MLA layers a decode step runs over ``caches``: the depth of each
    latent stack ([G, B, S, r]), one for an unstacked [B, S, r] latent."""
    return sum(leaf.shape[0] if leaf.ndim == 4 else 1
               for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]
               if getattr(path[-1], "key", None) == "c_kv")


# A decode block crosses the host↔device boundary as one int32 array each
# way, slot axis last (so it shards like the slots).  In, [5, B]: cur, pos,
# live (0/1), remaining, temps (float32 bits).  Out, [4K+3, B]: toks,
# emitted, done_now, finite ([K, B] each, flags 0/1), then cur, pos, live.

def pack_block_inputs(cur: np.ndarray, pos: np.ndarray, live: np.ndarray,
                      remaining: np.ndarray, temps: np.ndarray) -> np.ndarray:
    """Host side: the block's five per-slot inputs as one [5, B] int32."""
    return np.stack([cur, pos, live, remaining,
                     temps.astype(np.float32).view(np.int32)]
                    ).astype(np.int32, copy=False)


def unpack_block_inputs(packed: jax.Array):
    """Device side of :func:`pack_block_inputs`; temps come back bit-exact."""
    cur, pos, live, remaining, temps = packed
    return (cur, pos, live != 0, remaining,
            jax.lax.bitcast_convert_type(temps, jnp.float32))


def pack_block_outputs(toks, emitted, done_now, finite, cur, pos,
                       live) -> jax.Array:
    """Device side: the [K, B] tick outputs and the [B] carry vectors as one
    [4K+3, B] int32."""
    i32 = lambda a: a.astype(jnp.int32)
    return jnp.concatenate([i32(toks), i32(emitted), i32(done_now),
                            i32(finite), i32(cur)[None], i32(pos)[None],
                            i32(live)[None]])


def unpack_block_outputs(packed: np.ndarray, k: int):
    """Host side of :func:`pack_block_outputs`: (toks, emitted, done_now,
    finite, cur, pos, live).  Every array but ``toks`` is a fresh writable
    one: the quarantine pass masks ``emitted``/``done_now``, and ``_admit``
    writes the ``cur``/``pos``/``live`` mirrors."""
    flag = lambda i: packed[i * k:(i + 1) * k] != 0
    return (packed[:k], flag(1), flag(2), flag(3), packed[4 * k].copy(),
            packed[4 * k + 1].copy(), packed[4 * k + 2] != 0)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0   # 0 = greedy
    priority: int = 1          # scheduler class; smaller = more urgent
    # TTL budget in seconds from submission (None = no deadline).  Honored
    # at admission (deadline_s <= 0 expires on the spot), in queue, and
    # mid-decode: expired requests retire with finish_reason
    # "expired:queue" (never dispatched) or "expired:decode" (a slot was
    # committed), and their slots are reused the same tick.
    deadline_s: float | None = None
    deadline_at: float | None = None     # absolute (stamped at submit)
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    dispatched_at: float | None = None   # popped from the queue (slot found)
    first_token_at: float | None = None
    done_at: float | None = None
    retired_at: float | None = None      # == done_at; every path stamps it
    finish_reason: str | None = None
    truncated: bool = False     # prompt cut to the admission limit
    prefix_hit_tokens: int = 0  # prompt steps served from the prefix cache
    shard: int | None = None    # data shard placed on (None = unsharded)


@dataclasses.dataclass
class _PrefillJob:
    """A resumable prompt scan bound to a reserved slot."""

    req: Request
    slot: int
    caches: PyTree            # B=1, S_max decode-layout state
    pos: int = 0              # prompt tokens consumed so far
    logits: Any = None        # last-token logits of the latest chunk (device)


class DecodeServer:
    def __init__(self, cfg: ModelConfig, params: PyTree, num_slots: int, max_seq: int,
                 eos_id: int | None = None, seed: int = 0,
                 block_k: int = DEFAULT_BLOCK_K, persistent: bool = False,
                 prefill_chunk: int = 0,
                 prefix_cache_bytes: int = 0,
                 scheduler: Scheduler | SchedulerConfig | None = None,
                 prefill_chunks_per_tick: int = 1,
                 prefill_adaptive: bool = False,
                 obs: obs_lib.Observability | None = None,
                 faults: "faults_lib.FaultPlan | None" = None,
                 watchdog_s: float | None = None,
                 plan: ShardPlan | None = None):
        self.cfg, self.params = cfg, params
        self.B, self.S = num_slots, max_seq
        # Mesh placement (README §Sharded serving): ``plan`` maps the slot
        # pool onto the mesh's data axis in contiguous per-shard blocks and
        # TP-factors the gate contractions over ``model``.  plan=None is the
        # single-device server, bit for bit.
        self.plan = plan
        self.dp = plan.dp if plan is not None else 1
        self._slots_per_shard = (plan.validate_slots(num_slots)
                                 if plan is not None else num_slots)
        self.eos_id = eos_id
        self.block_k = block_k
        self.persistent = persistent
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_chunks_per_tick = max(1, int(prefill_chunks_per_tick))
        # Adaptive chunk sizing: when NO slot is decoding, a fixed chunk
        # buys nothing (there is no live stream to protect from head-of-line
        # blocking) and costs a dispatch + host sync per chunk — so an
        # uncontended tick drains pending prefill jobs whole, and the chunk
        # bound re-engages the moment any slot is live.  Opt-in: the fixed
        # bound stays the default contract (tests assert it).
        self.prefill_adaptive = bool(prefill_adaptive)
        if self.prefill_adaptive and self.prefill_chunk <= 0:
            raise ValueError(
                "prefill_adaptive=True requires prefill_chunk > 0 "
                "(adaptive sizing adapts the chunked path; unchunked "
                "prefill is already one-shot)")
        # Per-server observability scope: counters always on (they ARE the
        # stats() numbers), tracing opt-in (obs=Observability(trace=True)).
        self.obs = obs if obs is not None else obs_lib.Observability()
        self._tr = self.obs.tracer
        self._tr.thread_name(0, "server")
        # One PrefixCache per data shard (1/dp of the byte budget each,
        # shard-labeled counters): a hit is only a hit on the shard whose
        # slots hold the checkpointed batch rows, so admission probes every
        # shard's tree (peek_depth) and places the request shard-affinely.
        if prefix_cache_bytes:
            if plan is None:
                self.prefix_caches = [PrefixCache(prefix_cache_bytes,
                                                  metrics=self.obs.metrics)]
            else:
                per_shard = max(1, int(prefix_cache_bytes) // self.dp)
                self.prefix_caches = [
                    PrefixCache(per_shard, metrics=self.obs.metrics, shard=s)
                    for s in range(self.dp)]
        else:
            self.prefix_caches = None
        # back-compat alias for the unsharded server's single cache
        self.prefix_cache = (self.prefix_caches[0]
                             if self.prefix_caches and plan is None else None)
        if isinstance(scheduler, Scheduler):
            self.scheduler = scheduler
            self.scheduler.prompt_limit = self.scheduler.prompt_limit or (max_seq - 1)
        else:
            self.scheduler = Scheduler(scheduler, prompt_limit=max_seq - 1,
                                       metrics=self.obs.metrics)
        # Robustness layer (README §Robustness): an explicit FaultPlan wins;
        # otherwise the ambient plan installed via repro.runtime.faults is
        # consulted *per fire* so tests can arm/disarm around a live server.
        # With no plan anywhere, every fault check is a single `is None`.
        self.faults = faults
        self._watch = Watchdog(watchdog_s) if watchdog_s else None
        self._last_work = 0                 # progress marker for the watchdog
        self.caches = lm.init_cache(cfg, num_slots, max_seq)
        self._repl = None
        if plan is not None and not plan.fold_data:
            # Commit the decode state to the mesh: slot (batch) axis of every
            # cache leaf over the data axis, params replicated over data with
            # TP factors over model (fsdp=False — the data axis carries
            # slots, not ZeRO shards).  From here on every jitted driver
            # (decode_step, block scan, prefill/chunk fns) runs as one SPMD
            # program over the mesh; GSPMD inserts the gate all-reduce at
            # the TP contraction boundary.
            # A fold_data plan skips this block on purpose: its shards are
            # logical slot pools decoded as C-slow streams through one
            # fused dispatch (see ShardPlan docstring), so the state stays
            # single-device exactly like plan=None.
            self.params = jax.device_put(
                self.params, plan.param_shardings(cfg, self.params))
            self.caches = jax.device_put(
                self.caches, plan.cache_shardings(cfg, self.caches))
            self._repl = plan.replicated()
        self.pos = np.zeros(num_slots, np.int32)        # next write position
        self.live = np.zeros(num_slots, bool)
        self.reserved = np.zeros(num_slots, bool)       # prefill job in flight
        self.quarantined = np.zeros(num_slots, bool)    # awaiting state scrub
        self.slot_req: list[Request | None] = [None] * num_slots
        self._inflight: dict[int, Request] = {}         # uid -> admitted req
        self.cur_tokens = np.zeros(num_slots, np.int32)
        self.completed: list[Request] = []
        self.key = jax.random.PRNGKey(seed)
        # The decode cache exists once: every program that updates it takes
        # it donated (decode step, decode block, splice, slot fill), so an
        # update is in place and the old handle is dead the moment it runs.
        self._decode = self._on_mesh(jax.jit(
            lambda p, t, c, pos: lm.decode_step(p, cfg, t, c, pos),
            donate_argnums=2))

        def splice(caches, src, b):
            # looked up when traced, so a patched module attribute reaches it
            return splice_cache(caches, src, b, max_seq)

        self._splice_fn = jax.jit(splice, donate_argnums=0)
        self._poison_fn = jax.jit(functools.partial(
            fill_slot, num_slots=num_slots, floating_only=True), donate_argnums=0)
        self._scrub_fn = jax.jit(functools.partial(
            fill_slot, value=0, num_slots=num_slots, floating_only=False),
            donate_argnums=0)
        self._prefill = self._on_mesh(
            jax.jit(lambda p, t: lm.prefill(p, cfg, t)))
        self._chunk_fns: dict[int, Callable] = {}       # chunk len -> jitted
        self._block_fns: dict[int, Callable] = {}       # K -> jitted K-step loop
        self._jobs: list[_PrefillJob] = []
        self._job_rr = 0                                # round-robin cursor
        # Telemetry lives in the per-server registry; handles are cached here
        # so the hot loop never does a registry lookup.  Decode-phase sync
        # accounting (prefill excluded): the acceptance metric is host
        # round-trips per generated token.  Both modes amortize over the
        # live slots, so step() reports ~1/live and step_block() ~1/(K·live);
        # at equal occupancy the persistent/legacy ratio is the K× win.
        m = self.obs.metrics
        self._m_syncs = m.counter("decode_syncs",
                                  "host round-trips in the decode phase")
        self._m_d2h = m.counter("block_transfers",
                                "decode-block boundary transfers", dir="d2h")
        self._m_h2d = m.counter("block_transfers",
                                "decode-block boundary transfers", dir="h2d")
        self._m_tokens = m.counter("decoded_tokens", "tokens generated")
        # MLA latent cache rows the decode kernel reads (each slot's tiles up
        # to its position) against the rows the cache reserves; counted on
        # the host from the positions, for MLA models only
        self._mla_layers = mla_layers(self.caches)
        lat_help = "MLA latent cache rows: read by decode, or reserved"
        self._m_lat_read = m.counter("latent_rows", lat_help, kind="read")
        self._m_lat_reserved = m.counter("latent_rows", lat_help, kind="reserved")
        # prefill-phase telemetry: per-tick boundedness + cache savings
        self._m_prompt_steps = m.counter("prompt_steps_computed",
                                         "prompt tokens run on device")
        self._m_chunks = m.counter("prefill_chunks_run", "chunk dispatches")
        self._m_tick_max = m.gauge(
            "max_prompt_steps_per_tick",
            "high-watermark of per-tick prompt work (boundedness proof)")
        self._m_tick_contended = m.gauge(
            "max_prompt_steps_contended_tick",
            "high-watermark of per-tick prompt work on ticks where a live "
            "slot was decoding — the bound adaptive prefill must honor")
        self._m_live = m.gauge("live_slots", "slots decoding")
        self._h_ttft = m.histogram("ttft_ms", "submit -> first token")
        self._h_tpot = m.histogram("tpot_ms", "per-token decode latency")
        self._h_queue = m.histogram("queue_wait_ms",
                                    "submit -> dispatch (or terminal event "
                                    "for requests that never dispatched)")
        # robustness telemetry
        self._m_quar = m.counter("slots_quarantined",
                                 "slots retired on non-finite state")
        self._m_disp_retries = m.counter(
            "decode_dispatch_retries",
            "decode ticks aborted on a transient dispatch error")
        self._m_stalled = m.counter(
            "server_stalled", "watchdog firings (no progress in bound)")
        # per-shard telemetry: token counters labeled shard=N
        self._m_tokens_shard = (
            [m.counter("decoded_tokens_shard",
                       "tokens generated by data shard", shard=s)
             for s in range(self.dp)]
            if plan is not None else None)
        self._tick_prompt_steps = 0
        self._tick_uncontended = True       # no slot is live before tick 0

    # registry-backed views of the pre-obs counter attributes ---------------

    @property
    def decode_syncs(self) -> int:
        return int(self._m_syncs.value)

    @property
    def decoded_tokens(self) -> int:
        return int(self._m_tokens.value)

    @property
    def prompt_steps_computed(self) -> int:
        return int(self._m_prompt_steps.value)

    @property
    def prefill_chunks_run(self) -> int:
        return int(self._m_chunks.value)

    @property
    def max_prompt_steps_per_tick(self) -> int:
        return int(self._m_tick_max.value)

    @property
    def max_prompt_steps_contended_tick(self) -> int:
        return int(self._m_tick_contended.value)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Admission-controlled enqueue.  Rejected requests complete
        immediately with ``finish_reason='rejected:<reason>'`` and expired
        ones with ``'expired:queue'`` — every path gets latency stamps."""
        now = time.perf_counter()
        req.submitted_at = now
        if req.deadline_s is not None:
            req.deadline_at = now + req.deadline_s
            if req.deadline_s <= 0:   # dead on arrival: expire before admit
                self._retire(req, now, "expired:queue")
                return False
        if req.uid in self._inflight:
            # duplicate uid among queued/prefilling/decoding requests: the
            # first holder keeps its identity; the duplicate fails fast
            req.finish_reason = f"rejected:{REJECT_DUPLICATE_UID}"
            self.obs.metrics.counter("sched_rejected", "admission rejections",
                                     reason=REJECT_DUPLICATE_UID).inc()
            self._retire(req, now, req.finish_reason)
            return False
        admitted, _reason = self.scheduler.admit(req, now=now)
        for victim in self.scheduler.drain_evicted():
            self._retire(victim, now, victim.finish_reason)
        if not admitted:
            self._retire(req, now, req.finish_reason)
        else:
            self._inflight[req.uid] = req
        return admitted

    def _free_slot(self, shard: int | None = None) -> int | None:
        """First free slot — in ``shard``'s contiguous block when given,
        anywhere in the pool otherwise."""
        slots = (range(self.B) if shard is None
                 else self.plan.slots_of_shard(shard, self.B))
        for b in slots:
            if not self.live[b] and not self.reserved[b] \
                    and not self.quarantined[b]:
                return b
        return None

    # -- mesh placement helpers (all trivial when plan is None) -------------

    def _shard_of(self, b: int) -> int:
        return 0 if self.plan is None else b // self._slots_per_shard

    def _pc(self, shard: int) -> PrefixCache | None:
        """The prefix cache owning ``shard``'s slots (the single cache when
        unsharded)."""
        if self.prefix_caches is None:
            return None
        return self.prefix_caches[shard if self.plan is not None else 0]

    def _to_mesh(self, tree: PyTree) -> PyTree:
        """Lift a splice source onto the mesh (replicated).  Eager splices
        mixing a mesh-committed destination with a single-device source
        raise in jax; every B=1 prefill state and prefix checkpoint passes
        through here before touching the sharded slot arrays.  No-op when
        unsharded or folded (state is single-device in both)."""
        return tree if self._repl is None else jax.device_put(tree, self._repl)

    def _shard_load(self, shard: int) -> int:
        return sum(1 for b in self.plan.slots_of_shard(shard, self.B)
                   if self.live[b] or self.reserved[b])

    def _place(self, req: Request) -> int:
        """Shard-affine placement: among shards with a free slot, prefer the
        one whose prefix cache holds the deepest checkpoint for this prompt
        (ties → least loaded, then lowest id); without prefix caches it is
        pure least-loaded balancing."""
        free = [s for s in range(self.dp)
                if self._free_slot(shard=s) is not None]
        if self.prefix_caches is not None:
            return min(free, key=lambda s: (
                -self.prefix_caches[s].peek_depth(req.prompt),
                self._shard_load(s), s))
        return min(free, key=lambda s: (self._shard_load(s), s))

    def _retire(self, req: Request, now: float, reason: str) -> None:
        req.done_at = req.retired_at = now
        req.finish_reason = req.finish_reason or reason
        if self._inflight.get(req.uid) is req:
            del self._inflight[req.uid]
        self.completed.append(req)
        self._observe_retire(req, now)

    def _observe_retire(self, req: Request, now: float) -> None:
        """Latency metrics + the retroactive per-request trace track.

        TTFT/TPOT are *derived from the same timestamps the spans carry*, so
        the metrics snapshot and the trace always agree.  Spans land on track
        ``tid = uid + 1``: a ``request`` span containing queue_wait →
        prefill → decode children (parent/child by timestamp containment,
        per the Chrome trace-event format)."""
        self.obs.metrics.counter(
            "requests_completed", "retired requests by finish reason",
            reason=(req.finish_reason or "unknown").split(":")[0]).inc()
        n_out = len(req.out_tokens)
        if req.first_token_at is not None:
            self._h_ttft.observe((req.first_token_at - req.submitted_at) * 1e3)
            if n_out > 1 and req.done_at is not None:
                self._h_tpot.observe(
                    (req.done_at - req.first_token_at) / (n_out - 1) * 1e3)
        if req.dispatched_at is not None:
            self._h_queue.observe((req.dispatched_at - req.submitted_at) * 1e3)
        elif req.submitted_at:
            # rejected / expired-in-queue: the failure path still lands in
            # the queue-wait histogram (time queued before the terminal
            # event) so the obs latency view never silently skips failures
            self._h_queue.observe((now - req.submitted_at) * 1e3)
        tr = self._tr
        if not tr.enabled:
            return
        tid = req.uid + 1
        tr.thread_name(tid, f"req {req.uid}")
        t_sub = tr.to_us(req.submitted_at)
        t_done = max(tr.to_us(now), t_sub)
        args = {"uid": req.uid, "prompt_tokens": len(req.prompt),
                "out_tokens": n_out,
                "finish_reason": req.finish_reason,
                "prefix_hit_tokens": req.prefix_hit_tokens}
        if req.shard is not None:
            args["shard"] = req.shard
        tr.complete("request", t_sub, t_done - t_sub, cat="request", tid=tid,
                    args=args)
        t_disp = min(tr.to_us(req.dispatched_at), t_done) \
            if req.dispatched_at is not None else t_done
        tr.complete("queue_wait", t_sub, t_disp - t_sub, cat="request",
                    tid=tid)
        if req.first_token_at is not None:
            t_first = min(tr.to_us(req.first_token_at), t_done)
            tr.complete("prefill", t_disp, t_first - t_disp, cat="request",
                        tid=tid)
            tr.complete("decode", t_first, t_done - t_first, cat="request",
                        tid=tid, args={"tokens": n_out})

    # ------------------------------------------------------------------
    # robustness: fault points, quarantine, deadlines, cancellation
    # ------------------------------------------------------------------

    def _fire(self, point: str):
        """Consult the server's (or ambient) fault plan at ``point``.  One
        ``is None`` check when no plan is installed."""
        spec = faults_lib.fire(point, self.faults)
        if spec is not None:
            self.obs.metrics.counter("faults_injected", "injected faults",
                                     point=point).inc()
        return spec

    def _fault_slot(self, spec) -> int | None:
        """Deterministically pick the poisoned slot: the rule's payload may
        pin ``slot=``; otherwise the point's seeded RNG chooses among the
        live slots (replayable for a fixed workload)."""
        if "slot" in spec.payload:
            b = int(spec.payload["slot"])
            return b if self.live[b] else None
        live = [b for b in range(self.B) if self.live[b]]
        if not live:
            return None
        plan = self.faults if self.faults is not None else faults_lib.get_plan()
        return plan.rng(spec.point).choice(live)

    def _poison_slot(self, b: int, mode: str = "nan") -> None:
        """Write NaN/Inf into slot ``b``'s cache state (batch axis 1 == B
        leaves only) — the injected effect of the carry/splice fault points.
        Other slots' rows are untouched, so survivors stay bit-identical."""
        bad = float("nan") if mode == "nan" else float("inf")
        self.caches = self._poison_fn(self.caches, b, bad)

    def _scrub_slot(self, b: int) -> None:
        """Zero slot ``b``'s cache rows — quarantined state must never leak
        into the next request admitted to the slot."""
        self.caches = self._scrub_fn(self.caches, b)

    def _quarantine(self, b: int, now: float) -> None:
        """Retire slot ``b``'s request with ``error:nonfinite`` and pull the
        slot from service until its state is scrubbed (start of next tick).
        Only this slot is touched: the batch stays live and survivors'
        token streams are bit-identical to an uninjected run."""
        req = self.slot_req[b]
        if req is not None:
            self._retire(req, now, "error:nonfinite")
        self.slot_req[b] = None
        self.live[b] = False
        self.quarantined[b] = True
        self._m_quar.inc()
        if self.plan is not None:
            self.obs.metrics.counter("slots_quarantined_shard",
                                     "quarantines by data shard",
                                     shard=self._shard_of(b)).inc()

    def _scrub_quarantined(self) -> None:
        for b in range(self.B):
            if self.quarantined[b]:
                self._scrub_slot(b)
                self.quarantined[b] = False

    def _reap_deadlines(self, now: float) -> None:
        """Retire every expired request — queued (``expired:queue``), mid-
        prefill, or mid-decode (``expired:decode``).  Runs at the head of
        the tick, so freed slots are re-admitted the same tick."""
        for req in self.scheduler.reap_expired(now):
            self._retire(req, now, "expired:queue")
        for job in [j for j in self._jobs
                    if j.req.deadline_at is not None
                    and now >= j.req.deadline_at]:
            self._jobs.remove(job)
            self.reserved[job.slot] = False
            self._retire(job.req, now, "expired:decode")
        for b in range(self.B):
            req = self.slot_req[b]
            if req is not None and self.live[b] \
                    and req.deadline_at is not None \
                    and now >= req.deadline_at:
                self._retire(req, now, "expired:decode")
                self.live[b] = False
                self.slot_req[b] = None

    def cancel(self, uid: int) -> bool:
        """Cancel a request anywhere in flight (queued, prefilling, or
        decoding).  Retires it with ``finish_reason="cancelled"``; the freed
        slot is reused at the next tick's admission pass."""
        now = time.perf_counter()
        req = self.scheduler.remove(uid)
        if req is not None:
            self._retire(req, now, "cancelled")
            return True
        for job in self._jobs:
            if job.req.uid == uid:
                self._jobs.remove(job)
                self.reserved[job.slot] = False
                self._retire(job.req, now, "cancelled")
                return True
        for b in range(self.B):
            req = self.slot_req[b]
            if req is not None and req.uid == uid:
                self._retire(req, now, "cancelled")
                self.live[b] = False
                self.slot_req[b] = None
                return True
        return False

    def _abort_inflight(self, reason: str, now: float) -> None:
        """Structured abort: every in-flight request retires with
        ``reason`` (stall recovery — nothing awaits forever, nothing
        silently disappears)."""
        while True:
            req = self.scheduler.next_request(now=now)
            if req is None:
                break
            self._retire(req, now, reason)
        for job in list(self._jobs):
            self.reserved[job.slot] = False
            self._retire(job.req, now, reason)
        self._jobs.clear()
        for b in range(self.B):
            req = self.slot_req[b]
            if req is not None:
                self._retire(req, now, reason)
                self.live[b] = False
                self.slot_req[b] = None

    def _watchdog_check(self) -> None:
        """Fire the stall watchdog when work is in flight but no tick has
        made progress (tokens decoded, prompt steps run, or requests
        retired) within the wall-clock bound."""
        if self._watch is None:
            return
        now = time.perf_counter()
        work = (self.decoded_tokens + self.prompt_steps_computed
                + len(self.completed))
        if work != self._last_work:
            self._last_work = work
            self._watch.progress(now)
            return
        pending = bool(self.live.any() or self._jobs or len(self.scheduler))
        if pending and self._watch.stalled(now):
            self._m_stalled.inc()
            self._watch.fired += 1
            self._abort_inflight("error:stalled", now)
            self._watch.progress(now)

    def health(self) -> dict:
        """Readiness/liveness snapshot (also exported under
        ``stats()["health"]`` and by ``launch/serve.py``)."""
        stalled = int(self._m_stalled.value)
        quarantined = int(self.quarantined.sum())
        shed = int(self.obs.metrics.value("sched_rejected", reason="shed"))
        status = "stalled" if stalled else (
            "degraded" if quarantined or shed
            or int(self._m_quar.value) else "ok")
        out = {
            "status": status,
            "live_slots": int(self.live.sum()),
            "reserved_slots": int(self.reserved.sum()),
            "quarantined_slots": quarantined,
            "queued": len(self.scheduler),
            "slots_quarantined_total": int(self._m_quar.value),
            "dispatch_retries": int(self._m_disp_retries.value),
            "stalled_events": stalled,
            "watchdog_s": self._watch.bound_s if self._watch else None,
            "last_progress_idle_s":
                self._watch.idle_s() if self._watch else None,
        }
        if self.plan is not None:
            out["mesh"] = self.plan.describe()
            out["quarantined_by_shard"] = [
                sum(int(self.quarantined[b]) for b in
                    self.plan.slots_of_shard(s, self.B))
                for s in range(self.dp)]
        plan = self.faults if self.faults is not None else faults_lib.get_plan()
        if plan is not None:
            out["faults"] = plan.report()
        return out

    def _start_request(self, req: Request, b: int,
                       first_logits: jax.Array | np.ndarray) -> None:
        """Go live after the prompt state is in slot ``b`` — or retire at
        admission when the token budget is already met by the prefill-sampled
        first token (the max_new_tokens=1 off-by-one fix).  ``first_logits``
        may still be on the device: fetching it is where the host waits for
        the prefill."""
        with self._tr.span("first_token", cat="admit", args={"uid": req.uid}):
            first = int(np.argmax(np.asarray(first_logits)))
            now = time.perf_counter()
            req.out_tokens.append(first)
            req.first_token_at = now
            hit_eos = self.eos_id is not None and first == self.eos_id
            if len(req.out_tokens) >= req.max_new_tokens or hit_eos:
                self._retire(req, now, "eos" if hit_eos else "max_tokens")
                return
            self.slot_req[b] = req
            self.live[b] = True
            self.pos[b] = len(req.prompt)
            self.cur_tokens[b] = first

    def _splice(self, uid: int, b: int, prefill_caches: PyTree) -> None:
        """Write a B=1 prompt state into slot ``b`` of the decode caches, in
        place (one jitted program that takes the caches donated)."""
        with self._tr.span("splice", cat="admit", args={"uid": uid}):
            self.caches = self._splice_fn(self.caches,
                                          self._to_mesh(prefill_caches), b)

    def _chunk_fn(self, c: int) -> Callable:
        fn = self._chunk_fns.get(c)
        if fn is None:
            cfg = self.cfg
            fn = self._chunk_fns[c] = self._on_mesh(jax.jit(
                lambda p, t, cc, pos: lm.prefill_chunk(p, cfg, t, cc, pos)
            ))
        return fn

    def _on_mesh(self, fn: Callable) -> Callable:
        """Trace and run a model program (prefill, chunk, decode step,
        decode block) with the plan's mesh in context when the state is
        physically sharded: Mosaic kernels inside it cannot be partitioned
        by GSPMD, and ``shard_map`` themselves over the context mesh instead
        (the generated stage replicated, the held experts divided over
        ``model``)."""
        if self._repl is None:
            return fn
        mesh = self.plan.mesh

        def call(*args):
            with jax.set_mesh(mesh):
                return fn(*args)

        return call

    def _cache_boundary(self, job: _PrefillJob) -> None:
        """Checkpoint the job's current state into the prefix cache.  Only
        chunk-grid-aligned boundaries are resumable (a resumed scan then
        recomputes the same chunk shapes as a cold run); the prompt-end
        boundary additionally carries last-token logits for full hits."""
        pc = self._pc(self._shard_of(job.slot))
        if pc is None or job.pos == 0:
            return
        aligned = self.prefill_chunk > 0 and job.pos % self.prefill_chunk == 0
        pc.insert(
            job.req.prompt[: job.pos],
            self._slice_prefix(job.caches, job.pos),
            logits=job.logits[0] if job.logits is not None else None,
            resumable=aligned,
        )

    def _slice_prefix(self, caches: PyTree, p: int) -> PyTree:
        """Trim full-attention KV leaves to the first ``p`` rows so stored
        checkpoints cost O(prefix), not O(S_max); window rings and
        recurrent/SSM states are position-free or ring-complete and stored
        as-is."""
        S = self.S

        def one(path, leaf):
            name = str(getattr(path[-1], "key", path[-1]))
            if hasattr(leaf, "ndim") and leaf.ndim >= 3 \
                    and name in _SEQ_LEAVES and leaf.shape[2] == S:
                return leaf[:, :, :p]
            return leaf

        return jax.tree_util.tree_map_with_path(one, caches)

    def _inflate_entry(self, entry) -> PyTree:
        """Re-expand a stored checkpoint to a full B=1, S_max cache.  Under a
        plan the fresh buffer is lifted first: stored checkpoints are mesh-
        committed, and eager splice ops reject mixed device sets."""
        fresh = self._to_mesh(lm.init_cache(self.cfg, 1, self.S))
        return self._splice_fn(fresh, self._to_mesh(entry.caches), 0)

    def _admit(self) -> None:
        """Fill free slots from the scheduler.  Admission is a prefix-cache
        lookup first: a full hit splices the stored state (0 recomputed
        prompt steps); a partial hit resumes chunked prefill mid-prompt;
        a miss starts a prefill job (chunked) or runs the one-shot B=1
        prefill (legacy), then SPLICES the resulting state into the slot —
        the production continuous-batching pattern (separate prefill
        program, shared decode program; other slots' states are untouched).
        """
        with self._tr.span("admit", cat="admit"):
            while True:
                if self._free_slot() is None:
                    return
                req = self.scheduler.next_request()
                if req is None:
                    return
                now = time.perf_counter()
                if req.max_new_tokens <= 0:
                    # budget already met: retire before spending any device work
                    self._retire(req, now, "max_tokens")
                    continue
                plen = len(req.prompt)
                if self.plan is None:
                    shard = 0
                    b = self._free_slot()
                else:
                    shard = self._place(req)
                    b = self._free_slot(shard=shard)
                    self.scheduler.record_placement(req, shard)
                pc = self._pc(shard)

                entry = None
                if pc is not None:
                    candidates = pc.lookup(req.prompt)
                    full = next((e for e in candidates
                                 if e.length == plen and e.logits is not None), None)
                    if full is not None:
                        self._splice(req.uid, b, full.caches)
                        spec = self._fire("prefix.splice")
                        if spec is not None:
                            # corrupted checkpoint splice: caught downstream by
                            # the per-slot non-finite detection, not here
                            self._poison_slot(b, spec.mode)
                        req.prefix_hit_tokens = plen
                        pc.record_hit(plen, full=True)
                        self._start_request(req, b, full.logits)
                        continue
                    if self.prefill_chunk > 0:
                        entry = next((e for e in candidates if e.resumable), None)

                if self.prefill_chunk > 0:
                    # adaptive uncontended admission: with no live slot to stall
                    # and no resumable prefix state to splice, the chunk job
                    # machinery only adds work (resumable chunks scan against
                    # the full [1, S] cache buffer; one-shot prefill touches
                    # [1, plen]) — fall through to the one-shot path, which is
                    # dispatch-identical to an unchunked server
                    adaptive_oneshot = (self.prefill_adaptive and entry is None
                                        and self._tick_uncontended
                                        and not self._jobs)
                    if not adaptive_oneshot:
                        # job states live on the mesh (replicated) so chunk fns
                        # consuming the mesh-sharded params never mix device sets
                        caches = self._to_mesh(
                            self._inflate_entry(entry) if entry is not None
                            else lm.init_cache(self.cfg, 1, self.S))
                        start = entry.length if entry is not None else 0
                        if pc is not None:
                            if entry is not None:
                                req.prefix_hit_tokens = start
                                pc.record_hit(start, full=False)
                            else:
                                pc.record_miss()
                        self.reserved[b] = True
                        self._jobs.append(_PrefillJob(req=req, slot=b,
                                                      caches=caches, pos=start))
                        continue

                # legacy one-shot prefill
                if pc is not None:
                    pc.record_miss()
                toks = jnp.asarray(np.array(req.prompt, np.int32)[None])
                with self._tr.span("prefill_oneshot", cat="prefill",
                                   args={"uid": req.uid, "tokens": plen}):
                    logits, pcaches = self._prefill(self.params, toks)
                self._m_prompt_steps.inc(plen)
                self._tick_prompt_steps += plen
                self._splice(req.uid, b, pcaches)
                if pc is not None:
                    pc.insert(req.prompt, pcaches, logits=logits[0],
                              resumable=False)
                self._start_request(req, b, logits[0])

    # ------------------------------------------------------------------
    # chunked prefill
    # ------------------------------------------------------------------

    def _advance_prefill(self) -> None:
        """Advance at most ``prefill_chunks_per_tick`` chunks, round-robin
        over in-flight jobs — the per-tick device work stays bounded by
        chunks·chunk_size prompt tokens regardless of prompt length.

        With ``prefill_adaptive``, an *uncontended* tick (no live decode
        slot) instead drains every pending job whole: chunking exists to
        bound the decode stall a long prompt inflicts on live streams, and
        with nothing decoding the fixed chunk only multiplies dispatches
        (the serve_mixed_chunked throughput + TTFT loss).  The per-chunk
        greedy parity is unchanged — a full-length chunk is the same scan
        as chained fixed chunks — and the moment any slot is live the
        fixed bound re-engages."""
        drain = (self.prefill_adaptive and self._jobs
                 and self._tick_uncontended)
        budget = len(self._jobs) if drain else self.prefill_chunks_per_tick
        for _ in range(budget):
            if not self._jobs:
                return
            self._job_rr %= len(self._jobs)
            job = self._jobs[self._job_rr]
            plen = len(job.req.prompt)
            c = plen - job.pos if drain \
                else min(self.prefill_chunk, plen - job.pos)
            toks = jnp.asarray(
                np.array(job.req.prompt[job.pos:job.pos + c], np.int32)[None])
            with self._tr.span("prefill_chunk", cat="prefill",
                               args={"uid": job.req.uid, "pos": job.pos,
                                     "chunk": c}):
                job.logits, job.caches = self._chunk_fn(c)(
                    self.params, toks, job.caches, jnp.int32(job.pos))
            job.pos += c
            self._m_prompt_steps.inc(c)
            self._tick_prompt_steps += c
            self._m_chunks.inc()
            self._cache_boundary(job)
            if job.pos >= plen:
                self._jobs.remove(job)
                self._splice(job.req.uid, job.slot, job.caches)
                self.reserved[job.slot] = False
                self._start_request(job.req, job.slot, job.logits[0])
            else:
                self._job_rr += 1

    def _begin_tick(self) -> None:
        with self._tr.span("begin_tick", cat="tick"):
            self._tick_prompt_steps = 0
            spec = self._fire("tick.slow")
            if spec is not None and spec.delay_s > 0:
                time.sleep(spec.delay_s)
            # scrub quarantined slots (deferred device work) and reap expired
            # requests BEFORE admission — freed slots are reused this same tick
            self._scrub_quarantined()
            self._reap_deadlines(time.perf_counter())
            # contention is a tick-level property, captured before admissions:
            # a slot is "live" here iff it was decoding when the tick began —
            # requests started later this tick never stalled on this tick's
            # prefill work, so that work doesn't count against the chunk bound
            self._tick_uncontended = not self.live.any()
            self._admit()
            self._advance_prefill()
            self._admit()   # full-hit admissions may free the tick for decode
            self._m_tick_max.set_max(self._tick_prompt_steps)
            if not self._tick_uncontended:
                self._m_tick_contended.set_max(self._tick_prompt_steps)
            self._m_live.set(int(self.live.sum()))

    # ------------------------------------------------------------------
    # decode drivers
    # ------------------------------------------------------------------

    def step(self) -> int:
        """One batched decode tick for all live slots.  Returns #live."""
        self._begin_tick()
        if not self.live.any():
            return 0
        spec = self._fire("decode.nan_carry")
        if spec is not None:
            b = self._fault_slot(spec)
            if b is not None:
                self._poison_slot(b, spec.mode)
        with self._tr.span("decode_step", cat="decode",
                           args={"live": int(self.live.sum())}):
            toks = jnp.asarray(self.cur_tokens[:, None])
            try:
                if self._fire("decode.dispatch") is not None:
                    raise TransientFault("injected decode.dispatch fault")
                logits, self.caches = self._decode(
                    self.params, toks, self.caches, jnp.asarray(self.pos)
                )
            except TransientFault:
                # transient dispatch error: abort the tick, retry next tick
                # (state untouched).  A tiny backoff keeps a permanently
                # failing dispatch from spinning the host; the watchdog
                # bounds the livelock.
                self._m_disp_retries.inc()
                time.sleep(0.001)
                return int(self.live.sum())
            with self._tr.span("device_sync", cat="sync"):
                logits = np.asarray(logits)
        self._m_syncs.inc()
        if self._mla_layers:
            self._count_latent_rows(self.pos[None])
        self.pos += self.live.astype(np.int32)
        now = time.perf_counter()
        spec = self._fire("decode.nan_logits")
        if spec is not None:
            b = self._fault_slot(spec)
            if b is not None:
                logits = logits.copy()
                logits[b] = (np.nan if spec.mode == "nan" else np.inf)
        # per-slot non-finite detection: poison (injected or real — an
        # overflowed carry, a bad checkpoint splice) quarantines ONLY the
        # affected slot; the rest of the batch proceeds bit-identically
        finite = np.isfinite(logits).all(axis=-1)
        for b in range(self.B):
            if not self.live[b]:
                continue
            if not finite[b]:
                self._quarantine(b, now)
                continue
            req = self.slot_req[b]
            if req.temperature > 0:
                self.key, sub = jax.random.split(self.key)
                nxt = int(jax.random.categorical(sub, jnp.asarray(logits[b]) / req.temperature))
                # the int() above is its own host↔device round-trip (the
                # sampled id travels back) — count it, or the legacy-vs-
                # persistent sync comparison flatters the legacy path
                self._m_syncs.inc()
            else:
                nxt = int(np.argmax(logits[b]))
            req.out_tokens.append(nxt)
            self._m_tokens.inc()
            if self._m_tokens_shard is not None:
                self._m_tokens_shard[self._shard_of(b)].inc()
            if req.first_token_at is None:
                req.first_token_at = now
            self.cur_tokens[b] = nxt
            full = len(req.out_tokens) >= req.max_new_tokens
            hit_eos = self.eos_id is not None and nxt == self.eos_id
            oom = self.pos[b] >= self.S - 1
            if full or hit_eos or oom:
                self._retire(req, now,
                             "eos" if hit_eos else
                             ("max_tokens" if full else "out_of_cache"))
                self.live[b] = False
                self.slot_req[b] = None
        return int(self.live.sum())

    # ------------------------------------------------------------------
    # persistent device-side decode
    # ------------------------------------------------------------------

    def _make_block_fn(self, k: int) -> Callable:
        """Build the jitted K-step inner loop.  The carry is exactly the
        server's device state — (caches, cur_tokens, pos, live, remaining,
        key) — so a block is semantically K applications of ``step()`` with
        sampling and retirement decided on device.  The server dispatches it
        through :meth:`_make_packed_block_fn`."""
        cfg, S = self.cfg, self.S
        eos = np.int32(-1 if self.eos_id is None else self.eos_id)

        def block(params, caches, cur, pos, live, remaining, temps, key):
            def tick(carry, _):
                caches, cur, pos, live, remaining, key = carry
                logits, caches = lm.decode_step(params, cfg, cur[:, None],
                                                caches, pos)
                logits = logits.astype(jnp.float32)
                pos = pos + live.astype(jnp.int32)
                key, sub = jax.random.split(key)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # temp=0 slots divide by a tiny epsilon — harmless, the
                # gumbel-argmax of scaled logits is discarded by the where.
                scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
                sampled = jax.random.categorical(sub, scaled).astype(jnp.int32)
                nxt = jnp.where(temps > 0, sampled, greedy)
                nxt = jnp.where(live, nxt, cur)          # dead slots idle
                emitted = live
                remaining = remaining - live.astype(jnp.int32)
                done_now = live & ((remaining <= 0) | (nxt == eos)
                                   | (pos >= S - 1))
                live = live & ~done_now
                # per-slot health: one all-reduce over the logits per tick
                # (negligible vs the gate contractions) so the host can
                # quarantine poisoned slots at the block boundary without
                # syncing the caches back
                finite = jnp.isfinite(logits).all(axis=-1)
                return (caches, nxt, pos, live, remaining, key), \
                    (nxt, emitted, done_now, finite)

            carry0 = (caches, cur, pos, live, remaining, key)
            carry, outs = jax.lax.scan(tick, carry0, None, length=k)
            return carry, outs

        return jax.jit(block)

    def _make_packed_block_fn(self, k: int) -> Callable:
        """The block ``step_block`` dispatches: the K-step scan of
        :meth:`_make_block_fn` behind one packed int32 array each way
        (:func:`pack_block_inputs`, :func:`pack_block_outputs`), so a block
        costs one upload and one fetch.  Named ``block`` like the scan it
        wraps: profilers show the decode program as ``jit_block``.  The
        caches are donated: the block updates them in place."""
        scan = self._make_block_fn(k)

        def block(params, caches, packed, key):
            cur, pos, live, remaining, temps = unpack_block_inputs(packed)
            carry, outs = scan(params, caches, cur, pos, live, remaining,
                               temps, key)
            caches, cur, pos, live, _, key = carry
            return caches, key, pack_block_outputs(*outs, cur, pos, live)

        return jax.jit(block, donate_argnums=1)

    def _count_latent_rows(self, ticks: np.ndarray) -> None:
        """``ticks`` [K, B]: the query position of every slot in each of K
        decode ticks."""
        tiles = int(mla_ops.slot_tiles(ticks, self.S).sum())
        self._m_lat_read.inc(tiles * mla_ops.tile_rows(self.S) * self._mla_layers)
        self._m_lat_reserved.inc(ticks.size * self.S * self._mla_layers)

    def step_block(self) -> int:
        """K decode ticks in ONE device dispatch; returns #live after.

        Host work per block: pack the per-slot inputs into one [5, B] array
        and upload it, fetch the block's one [4K+3, B] output (the K×B token
        block, its emitted/done/finite flags and the carry vectors; this
        fetch is the block's only host↔device sync), append to the
        per-request transcripts, retire finished requests.

        Timestamps (first_token_at / done_at) are stamped at the block
        boundary — the host cannot observe inner ticks without the very sync
        this path removes — so per-request latency is quantized up to K-1
        device ticks coarser than the per-token driver reports.
        """
        self._begin_tick()
        if not self.live.any():
            return 0
        live_n = int(self.live.sum())
        with self._tr.span("block_prep", cat="decode", args={"live": live_n}):
            spec = self._fire("decode.nan_carry") or self._fire("decode.nan_logits")
            if spec is not None:
                # the persistent driver samples on device, so both poison points
                # inject into the carry — the in-block finite check catches it
                b = self._fault_slot(spec)
                if b is not None:
                    self._poison_slot(b, spec.mode)
            k = self.block_k
            fn = self._block_fns.get(k)
            if fn is None:
                fn = self._block_fns[k] = self._on_mesh(self._make_packed_block_fn(k))
            temps = np.array(
                [r.temperature if r is not None else 0.0 for r in self.slot_req],
                np.float32)
            remaining = np.array(
                [r.max_new_tokens - len(r.out_tokens) if r is not None else 0
                 for r in self.slot_req], np.int32)
            packed_in = pack_block_inputs(self.cur_tokens, self.pos, self.live,
                                          remaining, temps)
            pos0 = self.pos
        with self._tr.span("decode_block", cat="decode",
                           args={"live": live_n, "k": k}):
            try:
                if self._fire("decode.dispatch") is not None:
                    raise TransientFault("injected decode.dispatch fault")
                packed_in = jnp.asarray(packed_in)      # the one upload
                self._m_h2d.inc()
                self.caches, self.key, packed_out = fn(
                    self.params, self.caches, packed_in, self.key)
            except TransientFault:
                self._m_disp_retries.inc()
                time.sleep(0.001)
                return int(self.live.sum())
            # the block's one sync: its packed outputs to the host
            with self._tr.span("device_sync", cat="sync"):
                packed_out = np.asarray(packed_out)
            self._m_d2h.inc()
            (toks, emitted, done_now, finite, self.cur_tokens, self.pos,
             self.live) = unpack_block_outputs(packed_out, k)
        with self._tr.span("post_block", cat="decode",
                           args={"emitted": int(emitted.sum())}):
            self._m_syncs.inc()
            if self._mla_layers:
                # a slot's query position moves on after each tick it is live
                self._count_latent_rows(pos0 + np.cumsum(emitted, axis=0) - emitted)
            now = time.perf_counter()
            # quarantine pass: a slot that went non-finite at inner tick t
            # produced garbage from t on — drop those emissions (and any bogus
            # device-side retirement) and retire the slot as error:nonfinite
            quarantine: list[int] = []
            for b in range(self.B):
                bad = emitted[:, b] & ~finite[:, b]
                if bad.any():
                    tb = int(np.argmax(bad))
                    emitted[tb:, b] = False
                    done_now[tb:, b] = False
                    quarantine.append(b)
            for t in range(k):
                for b in range(self.B):
                    if not emitted[t, b]:
                        continue
                    req = self.slot_req[b]
                    req.out_tokens.append(int(toks[t, b]))
                    self._m_tokens.inc()
                    if self._m_tokens_shard is not None:
                        self._m_tokens_shard[self._shard_of(b)].inc()
                    if req.first_token_at is None:
                        req.first_token_at = now
                    if done_now[t, b]:
                        nxt = int(toks[t, b])
                        reason = ("eos" if (self.eos_id is not None
                                            and nxt == self.eos_id) else
                                  ("max_tokens"
                                   if len(req.out_tokens) >= req.max_new_tokens
                                   else "out_of_cache"))
                        self._retire(req, now, reason)
                        self.slot_req[b] = None
            for b in quarantine:
                self._quarantine(b, now)
            return int(self.live.sum())

    # ------------------------------------------------------------------
    def tick(self) -> bool:
        """One scheduling quantum (prefill chunks + decode); True if the
        server still has work in flight."""
        if self.persistent:
            self.step_block()
        else:
            self.step()
        self._watchdog_check()
        return bool(self.live.any() or self._jobs or len(self.scheduler))

    def stats(self, reset: bool = False) -> dict:
        """Serving telemetry: decode host round-trips per generated token,
        prefill boundedness, prefix-cache hit/miss/eviction, scheduler,
        request-latency summaries.  Every number is a view over the server's
        :class:`~repro.obs.MetricsRegistry` — ``export_metrics`` snapshots
        of the same registry therefore always agree with this dict.

        ``reset=True`` zeroes the counters *after* building the dict, so the
        next call reports a fresh window (stored prefix-cache checkpoints and
        in-flight queue contents are untouched).
        """
        toks = max(self.decoded_tokens, 1)
        out = {
            "decode_syncs": self.decode_syncs,
            "decoded_tokens": self.decoded_tokens,
            "syncs_per_token": self.decode_syncs / toks,
            # decode-block boundary transfers per block sync (1 and 1)
            "d2h_per_block": self._m_d2h.value / max(self.decode_syncs, 1),
            "h2d_per_block": self._m_h2d.value / max(self.decode_syncs, 1),
            "prefill": {
                "prompt_steps_computed": self.prompt_steps_computed,
                "chunks_run": self.prefill_chunks_run,
                "chunk_size": self.prefill_chunk,
                "adaptive": self.prefill_adaptive,
                "max_prompt_steps_per_tick": self.max_prompt_steps_per_tick,
                "max_prompt_steps_contended_tick":
                    self.max_prompt_steps_contended_tick,
            },
            "latency": {
                "ttft_ms": self._h_ttft.summary(),
                "tpot_ms": self._h_tpot.summary(),
                "queue_wait_ms": self._h_queue.summary(),
            },
            "scheduler": self.scheduler.telemetry(),
            "health": self.health(),
        }
        if self._mla_layers:
            read, reserved = self._m_lat_read.value, self._m_lat_reserved.value
            out["latent_rows"] = {"read": int(read), "reserved": int(reserved),
                                  "share": read / max(reserved, 1)}
        if self.plan is not None:
            out["mesh"] = dict(
                self.plan.describe(),
                slots_per_shard=self._slots_per_shard,
                live_by_shard=[
                    sum(int(self.live[b]) for b in
                        self.plan.slots_of_shard(s, self.B))
                    for s in range(self.dp)],
                decoded_tokens_by_shard=[
                    int(c.value) for c in self._m_tokens_shard],
            )
        if self.prefix_caches:
            if self.plan is None:
                out["prefix_cache"] = self.prefix_cache.telemetry()
            else:
                per = [c.telemetry() for c in self.prefix_caches]
                agg = {k: sum(p[k] for p in per)
                       for k in ("hits", "partial_hits", "misses",
                                 "insertions", "evictions",
                                 "prompt_steps_saved", "bytes_in_use",
                                 "budget_bytes", "entries")}
                agg["per_shard"] = per
                out["prefix_cache"] = agg
        if reset:
            self.reset_stats()
        return out

    def reset_stats(self) -> None:
        """Zero every counter/histogram in the server's metrics scope.  The
        scheduler and prefix cache usually share the scope (one registry), in
        which case their resets are redundant-but-harmless; they matter when
        a caller injected a Scheduler with its own registry."""
        self.obs.metrics.reset()
        self.scheduler.reset_stats()
        for pc in self.prefix_caches or ():
            pc.reset_stats()

    def run_until_drained(self, max_ticks: int = 10_000,
                          persistent: bool | None = None) -> list[Request]:
        use_block = self.persistent if persistent is None else persistent
        step = self.step_block if use_block else self.step
        ticks = 0
        while (len(self.scheduler) or self._jobs or self.live.any()) \
                and ticks < max_ticks:
            step()
            self._watchdog_check()
            ticks += 1
        return self.completed
