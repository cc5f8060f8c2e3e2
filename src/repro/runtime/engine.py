"""Continuous-batching request engine (DESIGN.md §3.2).

The serving runtime the ROADMAP's "heavy traffic" north star asks for:
instead of the batch-synchronous demo loop (pre-form a batch, rebuild
streams and a plan from scratch, run it to completion, report one
amortized latency), :class:`RequestEngine` owns an explicit request
lifecycle

    admit -> stream -> plan -> waves -> postprocess -> respond

with cross-request reuse at every stage:

* **admit** — requests enter an admission queue with optional deadlines
  (earliest-deadline-first, FIFO among equals).  Nothing waits for a
  batch to "fill": every engine step coalesces whatever has arrived.
* **stream** — token streams come from an LRU
  :class:`~repro.core.token_stream.TokenStreamCache` keyed by
  (query tokens, alpha, provider): repeated or overlapping queries skip
  ``build_token_stream_batch`` entirely; the misses of a step build in
  ONE stacked sweep.
* **plan** — one long-lived :class:`~repro.core.scheduler.ExecutionPlan`
  absorbs joiners mid-flight (``plan.add_queries``): a request admitted
  while others are halfway through their partitions joins the very next
  wave.  Sound because a query's tiles read only its own theta carry and
  row-level numerics are schedule-invariant (DESIGN.md §3) — the final
  top-k is bit-identical to the one-shot ``search_batch`` path.
* **waves** — each step runs one wave: a tile per live request, each at
  its own next partition (``scheduler.run_wave``), or per-partition
  fused device programs (``scheduler.run_fused_wave``) through the
  engine-lifetime :func:`~repro.core.wave.wave_runner_for` runner.
  Batch shapes pad to the existing pow2 buckets, so steady-state serving
  triggers zero recompiles (tests/test_recompile.py).
* **respond** — per-request merge + true admit->respond latency from
  :class:`~repro.runtime.instrument.EngineCounters` (never an amortized
  batch figure).

The engine is single-threaded and synchronous — "continuous batching"
is a property of the schedule (mid-flight joins at wave boundaries), not
of host threading, exactly as in serving systems whose step loop owns
the batch (the vLLM lesson applied to set search).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..core.postprocess import VerifierPool
from ..core.scheduler import (ExecutionPlan, SchedulerStats, _exchange,
                              run_fused_wave, run_wave)
from ..core.search import KoiosIndex, merge_topk
from ..core.token_stream import (TokenStreamCache,
                                 build_token_stream_batch_cached)
from ..core.types import (QueryValidationError, SearchParams, SearchResult,
                          SearchStats, validate_query)
from .fault import (FaultConfig, FaultPlan, FleetMonitor, ReplicaCrash,
                    TransientVerifierError)
from .instrument import EngineCounters, RequestTrace, record, span


def _void_result() -> SearchResult:
    """The result payload of a non-served response (shed / failed): an
    empty top-k, never a partial one — served responses stay exactly
    bit-identical to the one-shot path or are not served at all."""
    return SearchResult(ids=np.zeros(0, np.int32),
                        lb=np.zeros(0, np.float32),
                        ub=np.zeros(0, np.float32), stats=SearchStats())


@dataclasses.dataclass
class _Request:
    """Engine-internal lifecycle record of one admitted request."""

    rid: int
    query: np.ndarray
    trace: RequestTrace
    arrival: float                       # visibility time (trace replay)
    seq: int                             # admission tiebreak (FIFO)
    qi: int = -1                         # plan query index once joined
    epoch: int = -1                      # collection epoch pinned at join
    pending: List[int] = dataclasses.field(default_factory=list)
    parts: Dict[int, SearchResult] = dataclasses.field(default_factory=dict)

    def priority(self) -> tuple:
        d = self.trace.deadline
        return (d if d is not None else float("inf"), self.seq)


@dataclasses.dataclass(frozen=True)
class EngineResponse:
    """What ``respond`` emits: the merged result + true per-request
    lifecycle timings (the numbers ``serve_batch`` used to fake with one
    amortized figure).

    ``status`` makes the outcome explicit (DESIGN.md §6) instead of
    implying success: ``ok`` = served, bit-identical to the one-shot
    path; ``shed`` = dropped before occupying a wave tile because its
    deadline was already unreachable (``result`` is empty); ``retried``
    = served ``ok`` after ``retries`` failover resubmissions (same
    exactness guarantee as ``ok``); ``failed`` = the retry budget ran
    out, no healthy replica existed, the admission queue was full
    (``overloaded``), or the query failed admission-time validation
    (``reason`` says which).

    ``epoch`` is the collection epoch the request was SERVED against
    (pinned at join, DESIGN.md §6.5): a served response is bit-identical
    to the one-shot path over that epoch's repository, whatever commits
    landed while it was in flight."""

    rid: int
    result: SearchResult
    latency_s: float                     # admit -> respond
    queue_s: float                       # admit -> first wave
    waves: int
    stream_hit: bool
    deadline_met: Optional[bool]
    status: str = "ok"                   # ok | shed | retried | failed
    retries: int = 0                     # failover resubmissions served
    reason: str = ""                     # shed/failed explanation
    epoch: int = 0                       # collection epoch served against

    @property
    def served(self) -> bool:
        return self.status in ("ok", "retried")


class RequestEngine:
    """Admission-queued, stream-cached, shape-bucketed search runtime.

    ``schedule``: ``"wave"`` drives host waves (works on any backend),
    ``"fused"`` runs each wave's per-partition groups as fused device
    programs: always on TPU (a provider the wave cannot serve raises),
    anywhere with ``params.fused='interpret'``; ``fused='off'``, or
    ``'auto'`` off-TPU, resolves to host waves
    (``core.wave.fused_available``).  ``self.schedule`` is the resolved
    name.  Results are bit-identical across both and to the one-shot
    ``KoiosSearch.search_batch`` (tests/test_engine.py).

    ``clock``/``sleep`` are injectable for deterministic trace-replay
    tests; real serving uses the monotonic wall clock.

    Collection state lives in a :class:`ShardedCollection` resource —
    pass ``collection=`` to serve an existing (possibly placed, possibly
    shared-with-other-replicas) resource, or let the constructor build a
    private one from ``coll``/``partitions``/``partition_by``
    (``indexes=`` adopts prebuilt partition indexes into a resource —
    benchmarks sharing one index build).  The engine borrows per-shard
    operand views; it owns no collection device arrays.
    """

    def __init__(self, coll, sim_provider,
                 params: Optional[SearchParams] = None,
                 partitions: int = 1, schedule: str = "wave",
                 partition_by: str = "sets",
                 bound_exchange: Optional[Callable] = None,
                 stream_cache_bytes: int = 64 << 20,
                 max_wave_requests: int = 64,
                 max_pending: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 indexes: Optional[Sequence[KoiosIndex]] = None,
                 collection=None,
                 shed_deadlines: bool = False,
                 fault_plan: Optional[FaultPlan] = None,
                 replica_id: int = 0,
                 monitor: Optional[FleetMonitor] = None):
        from .collection import ShardedCollection

        self.params = params or SearchParams()
        self.sim = sim_provider
        if collection is None:
            collection = (ShardedCollection.adopt(coll, indexes)
                          if indexes is not None else
                          ShardedCollection.build(coll, partitions,
                                                  by=partition_by))
        self.collection = collection
        # pin the epoch this engine serves: every joined request computes
        # against this consistent snapshot until resync() (DESIGN.md §6.5)
        self._epoch = collection.pin()
        self.coll = self._epoch.coll
        self.bound_exchange = bound_exchange
        self.clock = clock
        self._sleep = sleep
        self.max_wave_requests = int(max_wave_requests)
        # bounded admission: past max_pending, submit responds
        # status='failed' reason='overloaded' instead of growing without
        # bound (None = unbounded — the historical behavior)
        self.max_pending = max_pending if max_pending is None \
            else int(max_pending)
        self.partitions = self._epoch.shards

        assert schedule in ("wave", "fused"), schedule
        self._runner = None
        if schedule == "fused":
            from ..core.wave import fused_available, wave_runner_for
            if fused_available(self.params, sim_provider):
                self._runner = wave_runner_for(sim_provider, self.params)
            else:
                schedule = "wave"
        self.schedule = schedule

        # engine-lifetime shared machinery (the cross-request reuse)
        self.plan = ExecutionPlan(self.partitions, [], pool_coll=self.coll,
                                  epoch=self._epoch.epoch)
        self.pool = VerifierPool(self.coll, sim_provider, self.params)
        self.stream_cache = TokenStreamCache(max_bytes=stream_cache_bytes)
        self.stream_cache.set_epoch(self._epoch.epoch)
        self.counters = EngineCounters()

        self._streams: List[object] = []          # aligned with plan.queries
        self._theta: List[float] = []             # per-query carry
        self._tiles: Dict[int, Dict[int, object]] = {}   # qi -> pi -> tile
        self._rid = itertools.count()
        self._seq = itertools.count()
        self._arrivals: List[_Request] = []       # future visibility
        self._queue: List[_Request] = []          # admitted, awaiting join
        self._inflight: Dict[int, _Request] = {}  # rid -> joined request
        self._completed: List[EngineResponse] = []

        # ---- fault-tolerant serving plane (DESIGN.md §6) ----
        # shed_deadlines: drop requests whose deadline is already
        # unreachable BEFORE they occupy a wave tile (status='shed');
        # off by default — shedding changes which requests are answered,
        # so it is an explicit serving policy, never a silent one.
        self.shed_deadlines = bool(shed_deadlines)
        self.fault_plan = fault_plan
        self.replica_id = int(replica_id)
        self.monitor = monitor
        self._step_no = 0                         # 1-based after first step
        self._wave_ewma = 0.0                     # smoothed wave seconds
        self._last_wave = 0                       # tiles run by last step

        # ---- epoch rollout (DESIGN.md §6.5) ----
        # standalone engines resync at the first drained step boundary
        # after a commit; a router serializes the rollout by granting
        # _resync_allowed to one behind replica at a time
        self._resync_allowed = True
        self._warm_sample: Optional[List[np.ndarray]] = None

    # ------------------------------------------------------------- admit
    def submit(self, query, deadline: Optional[float] = None,
               arrival: Optional[float] = None) -> int:
        """Admit one request; returns its request id.

        ``deadline`` (clock timestamp) orders the admission queue
        (earliest first) and is reported as met/missed on respond.
        ``arrival`` defers the request's *visibility* to the engine —
        trace replay for staggered-arrival benchmarks; the admit
        timestamp is the arrival time, so queue time is measured from
        when the request actually arrived.

        Admission is guarded (DESIGN.md §6): an invalid query (empty,
        non-integer, negative ids, or a non-finite embedding row for an
        in-vocab token) or a full admission queue (``max_pending``)
        responds ``status='failed'`` with a reason — a rid is still
        returned and the response flows through the normal channel, so
        callers never need a second error path."""
        rid = next(self._rid)
        now = self.clock()
        t_arr = now if arrival is None else float(arrival)
        try:
            query = validate_query(query, self.sim)
        except QueryValidationError as e:
            return self._reject(rid, t_arr, now, f"invalid query: {e}",
                                kind="invalid")
        if self.max_pending is not None \
                and self.pending() >= self.max_pending:
            return self._reject(
                rid, t_arr, now,
                f"overloaded (admission queue at max_pending="
                f"{self.max_pending})", kind="overloaded")
        req = _Request(
            rid=rid, query=np.asarray(query, np.int32),
            trace=RequestTrace(rid=rid, t_admit=t_arr, deadline=deadline),
            arrival=t_arr, seq=next(self._seq))
        if t_arr > now:
            self._arrivals.append(req)
            self._arrivals.sort(key=lambda r: (r.arrival, r.seq))
        else:
            self._queue.append(req)
        return rid

    def _reject(self, rid: int, t_arr: float, now: float, reason: str,
                kind: str) -> int:
        """Refuse admission with an explicit ``failed`` response (never
        an exception, never a silent drop, never a garbage top-k)."""
        trace = RequestTrace(rid=rid, t_admit=t_arr, status="failed")
        trace.t_respond = now
        record(f"engine:{kind}")
        if kind == "overloaded":
            self.counters.observe_overload()
        else:
            self.counters.observe_invalid()
        self.counters.observe_respond(trace)
        self._completed.append(EngineResponse(
            rid=rid, result=_void_result(),
            latency_s=max(now - t_arr, 0.0), queue_s=0.0, waves=0,
            stream_hit=False, deadline_met=None, status="failed",
            reason=reason, epoch=self._epoch.epoch))
        return rid

    def _admit_arrived(self, now: float) -> None:
        while self._arrivals and self._arrivals[0].arrival <= now:
            self._queue.append(self._arrivals.pop(0))

    # -------------------------------------------------------------- join
    def _join(self, now: float) -> None:
        """Coalesce queued requests into the in-flight cohort: fetch or
        build their streams (one stacked sweep for all of a step's
        misses) and absorb them into the plan mid-flight."""
        with span("koios.join"):
            room = self.max_wave_requests - len(self._inflight)
            if room <= 0 or not self._queue:
                return
            self._queue.sort(key=_Request.priority)
            joiners, self._queue = self._queue[:room], self._queue[room:]
            queries = [r.query for r in joiners]
            # per-request hit attribution: a duplicate of a query earlier in
            # the same join is served without a sweep too (matches the cache
            # counters' accounting of duplicate misses)
            hits, seen = [], set()
            for q in queries:
                key = self.stream_cache.key(q, self.params.alpha, self.sim)
                hits.append(self.stream_cache.contains(key) or key in seen)
                seen.add(key)
            with span("koios.stream"):
                streams = build_token_stream_batch_cached(
                    queries, self.sim, self.params.alpha, self.stream_cache,
                    use_kernel=self.params.stream_use_kernel,
                    interpret=self.params.interpret)
            qis, new_tiles = self.plan.add_queries(queries)
            for t in new_tiles:
                self._tiles.setdefault(t.qi, {})[t.pi] = t
            self._streams.extend(streams)
            self._theta.extend([0.0] * len(joiners))
            for req, qi, hit in zip(joiners, qis, hits):
                req.qi = qi
                req.epoch = self._epoch.epoch
                req.pending = list(range(len(self.partitions)))
                req.trace.stream_hit = bool(hit)
                self._inflight[req.rid] = req

    # -------------------------------------------------------------- waves
    def _run_wave_tiles(self, tiles) -> None:
        if self._runner is not None:
            by_pi: Dict[int, list] = {}
            for t in tiles:
                by_pi.setdefault(t.pi, []).append(t)
            for pi in sorted(by_pi):
                with span("koios.wave", shard=pi, B=len(by_pi[pi])):
                    run_fused_wave(self.plan, by_pi[pi], self._streams,
                                   self._theta, self.pool, self.params,
                                   self._runner)
        else:
            run_wave(self.plan, tiles, self._streams, self._theta,
                     self.pool, self.params)
        if self.bound_exchange is not None and self._inflight:
            # fold the mesh's all-reduce-max back into the live carries
            qis = [r.qi for r in self._inflight.values()]
            vec = _exchange(np.asarray([self._theta[qi] for qi in qis],
                                       np.float64), self.bound_exchange)
            for qi, v in zip(qis, vec):
                self._theta[qi] = max(self._theta[qi], float(v))

    def step(self) -> List[EngineResponse]:
        """One continuous-batching step: admit arrivals, shed the doomed
        (deadline already unreachable — BEFORE any wave tile is spent on
        them), join the queue, run one wave (a tile per live request at
        its next partition), respond to whoever finished.  Returns the
        step's responses.  Each step heartbeats into the attached
        :class:`FleetMonitor` (the router's health plane) and fires any
        :class:`FaultPlan` events addressed to this replica+step."""
        with span("koios.step", step=self._step_no + 1) as sp:
            out = self._step()
            sp.annotate(wave=self._last_wave)
        return out

    def _step(self) -> List[EngineResponse]:
        t_enter = self.clock()
        self._step_no += 1
        self._last_wave = 0
        verify_fault = False
        if self.fault_plan is not None:
            for ev in self.fault_plan.take(self.replica_id, self._step_no):
                if ev.kind == "crash":
                    raise ReplicaCrash(
                        f"replica {self.replica_id} crashed at engine "
                        f"step {self._step_no}")
                if ev.kind == "stall":
                    self._sleep(ev.stall_s)
                elif ev.kind == "verify_error":
                    verify_fault = True
        now = self.clock()
        self._admit_arrived(now)
        if self.shed_deadlines:
            self._shed_pass(now)
        depth = len(self._queue)
        # epoch rollout (DESIGN.md §6.5): behind the head epoch, the
        # in-flight cohort drains on its pinned snapshot and NO new
        # request joins — new admissions must see the committed epoch.
        # Resync happens at the first drained step boundary (immediately
        # for a standalone engine; when the router grants the rollout
        # slot for a fleet replica).
        if self.epoch_behind():
            if not self._inflight and self._resync_allowed:
                self.resync()
                self._join(now)
        else:
            self._join(now)
        if not self._inflight:
            self._heartbeat(t_enter)
            out, self._completed = self._completed, []
            return out

        wave, reqs = [], []
        for req in sorted(self._inflight.values(), key=_Request.priority):
            pi = req.pending.pop(0)
            tile = self._tiles[req.qi][pi]
            if req.trace.waves == 0:
                req.trace.t_first_wave = now
            req.trace.waves += 1
            wave.append(tile)
            reqs.append((req, pi))
        self.counters.observe_step(queue_depth=depth, wave_size=len(wave))
        self._last_wave = len(wave)
        if verify_fault:
            raise TransientVerifierError(
                f"replica {self.replica_id} verification fault at engine "
                f"step {self._step_no}")
        t_wave = self.clock()
        self._run_wave_tiles(wave)

        t_done = self.clock()
        dt = t_done - t_wave
        self._wave_ewma = (dt if self._wave_ewma == 0.0
                           else 0.5 * dt + 0.5 * self._wave_ewma)
        for req, pi in reqs:
            req.parts[pi] = self._tiles[req.qi][pi].result
            if not req.pending:
                self._respond(req, t_done)
        self._heartbeat(t_enter)
        out, self._completed = self._completed, []
        return out

    def _heartbeat(self, t_enter: float) -> None:
        if self.monitor is not None:
            self.monitor.heartbeat(self.replica_id, self._step_no,
                                   self.clock() - t_enter,
                                   epoch=self._epoch.epoch)

    # -------------------------------------------------------------- epoch
    @property
    def epoch(self) -> int:
        """The collection epoch this engine currently serves."""
        return self._epoch.epoch

    def epoch_behind(self) -> bool:
        """True when a commit installed a newer head epoch than the one
        this engine has pinned."""
        return self._epoch is not self.collection.head

    def resync(self) -> None:
        """Re-pin the head epoch at a step boundary: rebuild the plan /
        verifier pool over the new shard list, invalidate the stream
        cache's epoch key, release the old epoch's reader reference
        (the LAST reader out frees its exclusive device buffers), and
        re-warm the shard-local wave-config grid so the rollout does not
        recompile mid-traffic.  Requires a drained wave cohort — pinned
        in-flight requests NEVER migrate epochs (their bit-exactness is
        against the admission snapshot); queued requests join the new
        epoch on the very next step."""
        assert not self._inflight, "resync requires a drained wave cohort"
        old = self._epoch
        self._epoch = self.collection.pin()
        self.coll = self._epoch.coll
        self.partitions = self._epoch.shards
        self._streams, self._theta, self._tiles = [], [], {}
        self.plan = ExecutionPlan(self.partitions, [], pool_coll=self.coll,
                                  epoch=self._epoch.epoch)
        self.pool = VerifierPool(self.coll, self.sim, self.params)
        self.stream_cache.set_epoch(self._epoch.epoch)
        self.collection.release(old)
        record("engine:resync")
        self.counters.observe_resync()
        if self._warm_sample is not None:
            self._warmup_wave_grid(self._warm_sample)

    # ----------------------------------------------------------- shedding
    def _deadline_unreachable(self, req: _Request, now: float,
                              waves_left: int) -> bool:
        """True when even the optimistic service estimate (smoothed wave
        seconds x remaining partition waves) cannot meet the deadline.
        With no wave history yet the estimate is 0 — only requests whose
        deadline has ALREADY passed are shed (never a guess)."""
        d = req.trace.deadline
        return d is not None and now + self._wave_ewma * waves_left > d

    def _shed_pass(self, now: float) -> None:
        """Deadline-aware admission + wave sizing: shed doomed requests
        from the admission queue (before their stream is ever built) and
        from the in-flight cohort (before they occupy another tile of
        the wave being formed)."""
        waves_full = len(self.partitions)
        keep = []
        for req in self._queue:
            if self._deadline_unreachable(req, now, waves_full):
                self._shed(req, now, joined=False)
            else:
                keep.append(req)
        self._queue = keep
        for req in [r for r in self._inflight.values()
                    if self._deadline_unreachable(r, now, len(r.pending))]:
            self._shed(req, now, joined=True)

    def _shed(self, req: _Request, now: float, joined: bool) -> None:
        """Emit a ``status='shed'`` response without spending a wave tile
        (instrument event ``engine:shed`` is the audit trail)."""
        req.trace.t_respond = now
        req.trace.status = "shed"
        record("engine:shed")
        self.counters.observe_respond(req.trace)
        est = self._wave_ewma * (len(req.pending) if joined
                                 else len(self.partitions))
        self._completed.append(EngineResponse(
            rid=req.rid, result=_void_result(),
            latency_s=req.trace.latency_s, queue_s=max(req.trace.queue_s, 0.0),
            waves=req.trace.waves, stream_hit=req.trace.stream_hit,
            deadline_met=False, status="shed",
            reason=f"deadline unreachable (estimate {est:.4f}s, "
                   f"deadline {req.trace.deadline - now:+.4f}s away)",
            epoch=req.epoch if joined else self._epoch.epoch))
        if joined:
            self._retire(req)

    # ------------------------------------------------------------ respond
    def _respond(self, req: _Request, t_done: float) -> None:
        with span("koios.respond"):
            result = merge_topk([req.parts[pi] for pi in sorted(req.parts)],
                                self.params.k)
            req.trace.t_respond = t_done
            self.counters.observe_respond(req.trace)
            self._completed.append(EngineResponse(
                rid=req.rid, result=result,
                latency_s=req.trace.latency_s, queue_s=req.trace.queue_s,
                waves=req.trace.waves, stream_hit=req.trace.stream_hit,
                deadline_met=req.trace.deadline_met, epoch=req.epoch))
            self._retire(req)

    def _retire(self, req: _Request) -> None:
        """Release a joined request's plan/stream/tile state."""
        del self._inflight[req.rid]
        del self._tiles[req.qi]
        self._streams[req.qi] = None      # the LRU cache keeps the stream
        self._theta[req.qi] = 0.0
        remap = self.plan.retire_tiles([req.qi])
        if remap is not None:
            # the plan compacted its query ring (bounded plan size for
            # long-lived engines, DESIGN.md §9 item 9): shift every
            # qi-indexed engine structure through the same remap
            order = sorted(remap)        # old qis ascending == new order
            self._streams = [self._streams[old] for old in order]
            self._theta = [self._theta[old] for old in order]
            self._tiles = {remap[old]: tiles
                           for old, tiles in self._tiles.items()}
            for r in self._inflight.values():
                r.qi = remap[r.qi]

    # ---------------------------------------------------------- evacuate
    def evacuate(self) -> "tuple[List[EngineResponse], List[tuple]]":
        """Quarantine support (DESIGN.md §6): hand back everything this
        replica still owes — its buffered (already computed, still
        valid) responses plus a ``(rid, query, deadline)`` spec for
        every un-responded request — and reset all per-request state so
        the requests can be resubmitted elsewhere with no risk of a
        duplicate respond here.  Request-independent resources (stream
        cache, verifier pool, compiled wave programs, the borrowed
        collection) survive: a revived replica serves fresh requests
        immediately."""
        done, self._completed = self._completed, []
        pend = sorted(itertools.chain(self._arrivals, self._queue,
                                      self._inflight.values()),
                      key=lambda r: r.rid)
        specs = [(r.rid, r.query, r.trace.deadline) for r in pend]
        self._arrivals, self._queue = [], []
        self._inflight, self._tiles = {}, {}
        self._streams, self._theta = [], []
        self.plan = ExecutionPlan(self.partitions, [], pool_coll=self.coll,
                                  epoch=self._epoch.epoch)
        return done, specs

    # ------------------------------------------------------------- warmup
    def warmup(self, sample: Sequence[np.ndarray],
               reset_counters: bool = True) -> None:
        """Compile-warm the serving path before taking traffic.

        Serves pow2-sized cohorts of ``sample`` (stream sweep,
        refinement scan, solver, and wave shapes for every batch bucket
        the trace can coalesce), sweeps the SHARD-LOCAL fused wave-config
        grid (every shard x cohort bucket x the sample's pow2 event-chunk
        buckets plus a 2x guard bucket — steady-state queries landing one
        bucket above the sample still hit a compiled program), so
        steady-state serving — sharded or not — triggers zero recompiles
        (tests/test_recompile.py).  The verifier's weight program shares
        its (B, nq_pad, c_pad) keys with the solver, which the cohorts
        warm.  Standard request-engine startup
        practice; ``reset_counters`` wipes the warmup's traces from the
        metrics (the stream cache keeps its entries — that is warmup
        working as intended)."""
        sample = [np.asarray(q, np.int32) for q in sample]
        # kept for post-resync re-warm: a rollout re-sweeps the new
        # epoch's shard-local wave grid with the same sample
        self._warm_sample = sample if sample else None
        if sample:
            bs = 1
            while True:
                self.serve(sample[:bs])
                if bs >= len(sample):
                    break
                bs = min(2 * bs, len(sample))
            self._warmup_wave_grid(sample)
        if reset_counters:
            self.counters = EngineCounters()
            # scheduler-side counters (waves/rounds/...) are warmup work
            # too — reset them so summary() reflects only real traffic
            self.plan.stats = SchedulerStats(tiles=len(self.plan.tiles))

    def _warmup_wave_grid(self, sample: Sequence[np.ndarray]) -> None:
        """Sweep the shard-local fused wave-config grid (DESIGN.md §3.2).

        The serve() cohort sweep above compiles exactly the (shard,
        cohort-bucket, event-chunk-bucket) configs the SAMPLE's streams
        produce; live traffic with slightly heavier streams lands one
        pow2 chunk bucket up and would recompile mid-serve.  This pass
        walks the same doubling cohorts and, per shard, compiles the
        observed chunk bucket (an lru hit — free) plus its 2x guard
        bucket on an empty cohort (``WaveRunner.warm``), so every shard's
        near-neighborhood of the sample grid is compiled before traffic.
        Host-wave engines have no wave programs — nothing to do."""
        if self._runner is None:
            return
        from ..core.types import pow2
        from ..core.wave import _WAVE_CHUNK_GUARD, cohort_pads
        streams = build_token_stream_batch_cached(
            sample, self.sim, self.params.alpha, self.stream_cache,
            use_kernel=self.params.stream_use_kernel,
            interpret=self.params.interpret)
        chunk = self.params.chunk_size
        counts = [s.inv.posting_counts() for s in self.partitions]
        bs = 1
        while True:
            cohort_q, cohort_s = sample[:bs], streams[:bs]
            B_pad = pow2(len(cohort_q))
            t_pad, nq_pad, q_words = cohort_pads(cohort_q, cohort_s)
            for shard, cnt in zip(self.partitions, counts):
                buckets = set()
                for s in cohort_s:
                    n_events = int(cnt[s.token].sum())
                    if n_events:
                        buckets.add(pow2(max(1, -(-n_events // chunk))))
                for nc in sorted(b * g for b in buckets
                                 for g in _WAVE_CHUNK_GUARD):
                    self._runner.warm(shard, B_pad, nc, t_pad,
                                      nq_pad, q_words)
            if bs >= len(sample):
                break
            bs = min(2 * bs, len(sample))

    # -------------------------------------------------------------- drive
    def pending(self) -> int:
        """Requests anywhere in the lifecycle short of respond."""
        return len(self._arrivals) + len(self._queue) + len(self._inflight)

    def drain(self, max_idle_wait_s: float = 0.01) -> List[EngineResponse]:
        """Step until every submitted request (including future-dated
        arrivals) has responded.

        No busy-spin: an idle gap before a known future arrival sleeps
        the FULL gap in one call (arrivals are the only thing that can
        wake a single-threaded engine, so the historical 10ms-capped
        sleep just woke up ~100x/s to re-discover the same gap), and a
        step that moved nothing while in-flight work is still pending
        (a deferred/empty wave under shedding or fault injection) backs
        off exponentially, capped at ``max_idle_wait_s``."""
        out: List[EngineResponse] = []
        idle = max_idle_wait_s / 16
        while self.pending():
            n0 = len(out)
            out.extend(self.step())
            if len(out) > n0 or self._last_wave:
                idle = max_idle_wait_s / 16          # progress: reset
            elif self._inflight or self._queue:
                self._sleep(idle)                    # pending but stuck
                idle = min(2 * idle, max_idle_wait_s)
            elif self._arrivals:
                wait = self._arrivals[0].arrival - self.clock()
                if wait > 0:
                    self._sleep(wait)
        out.extend(self.step())           # flush any buffered responses
        return out

    def serve(self, queries: Sequence[np.ndarray],
              deadlines: Optional[Sequence[Optional[float]]] = None
              ) -> List[EngineResponse]:
        """Submit a batch and drain it; responses in request-id order."""
        for i, q in enumerate(queries):
            self.submit(q, deadline=deadlines[i] if deadlines else None)
        return sorted(self.drain(), key=lambda r: r.rid)

    def summary(self) -> dict:
        """Engine metrics incl. stream-cache and scheduler stats."""
        out = self.counters.summary(cache_stats=self.stream_cache.stats())
        out["schedule"] = self.schedule
        out["epoch"] = self.epoch
        out["scheduler"] = {
            "waves": self.plan.stats.waves,
            "rounds": self.plan.stats.rounds,
            "device_rounds": self.plan.stats.device_rounds,
            "fused_requests": self.plan.stats.fused_requests,
        }
        return out


@dataclasses.dataclass(frozen=True)
class RouterPolicy:
    """Failover knobs of the admission router (DESIGN.md §6).

    ``retry_budget`` bounds how many times one request may be
    resubmitted after its replica was quarantined (beyond it the
    request responds ``failed`` — never silently dropped);
    ``backoff_s`` is the base of the exponential resubmission delay
    (``backoff_s * 2**(attempt-1)``), so a flapping fleet is not
    hammered by the same request; ``revive_after_s`` is the quarantine
    cooldown after which a revivable (stalled / transient-error)
    replica rejoins the fleet — crashes are permanent."""

    retry_budget: int = 2
    backoff_s: float = 0.02
    revive_after_s: float = 0.25


class AdmissionRouter:
    """N :class:`RequestEngine` replicas over ONE logical collection
    behind a single front door (DESIGN.md §5), with a per-replica
    health plane (DESIGN.md §6).

    Every replica serves the SAME :class:`ShardedCollection` resource —
    per-shard device operands are uploaded once and borrowed by all, and
    identical (provider, params) pairs share compiled wave
    programs through ``wave_runner_for`` — so a replica costs one plan +
    one verifier pool + one stream cache, not another copy of the
    repository.  The router admits requests with a global request id,
    routes each to the least-loaded HEALTHY replica (fewest
    lifecycle-pending requests; round-robin among ties, so an idle
    fleet still spreads arrivals), and merges responses back into
    global-rid order.

    Health: every engine step heartbeats into the shared
    :class:`FleetMonitor`.  A replica that raises, exceeds the
    straggler bound for ``FaultConfig.straggler_patience`` steps, or
    hangs past ``FaultConfig.heartbeat_timeout`` within one step is
    quarantined: its un-responded requests are evacuated and resubmitted
    to healthy replicas over the same shared collection (no re-upload),
    with a bounded retry budget and exponential backoff
    (:class:`RouterPolicy`).  A request served after failover responds
    ``status='retried'``; one that exhausts the budget (or finds no
    healthy replica) responds ``status='failed'`` with a reason —
    never an unhandled exception.  Global response ordering (sorted
    global rids) is preserved across failovers because a resubmitted
    request keeps its gid.

    Exactness is per replica — every SERVED response is bit-identical
    to a one-shot ``KoiosSearch.search_batch`` over the same
    collection, whether it was served first-try or after failover, so
    neither routing nor recovery can perturb any served result
    (tests/test_sharded_collection.py, tests/test_fault.py)."""

    def __init__(self, coll, sim_provider,
                 params: Optional[SearchParams] = None, replicas: int = 2,
                 partitions: int = 1, partition_by: str = "sets",
                 collection=None, policy: RouterPolicy = RouterPolicy(),
                 fault_config: FaultConfig = FaultConfig(),
                 fault_plan: Optional[FaultPlan] = None, **engine_kwargs):
        from .collection import ShardedCollection

        assert replicas >= 1, replicas
        if collection is None:
            collection = ShardedCollection.build(coll, partitions,
                                                 by=partition_by)
        self.collection = collection
        self.policy = policy
        self.monitor = FleetMonitor(
            replicas, fault_config,
            clock=engine_kwargs.get("clock", time.monotonic))
        self.engines = [
            RequestEngine(None, sim_provider, params,
                          collection=collection, monitor=self.monitor,
                          replica_id=ei, fault_plan=fault_plan,
                          **engine_kwargs)
            for ei in range(replicas)]
        self.clock = self.engines[0].clock       # shared trace clock
        self._sleep = self.engines[0]._sleep
        self._rid = itertools.count()
        self._local: Dict[int, "tuple[int, int]"] = {}  # gid -> (eng, rid)
        self._gid: Dict["tuple[int, int]", int] = {}    # inverse
        self._rr = itertools.count()                    # tie-break cursor
        # ---- health / failover state ----
        self._quarantined: Dict[int, dict] = {}   # ei -> {t, reason, ...}
        self._attempts: Dict[int, int] = {}       # gid -> resubmissions
        self._failed: List[EngineResponse] = []   # buffered failed resp.
        self.quarantine_log: List[dict] = []      # audit trail (soak)
        self.retries = 0                          # resubmissions issued
        self.failures = 0                         # failed responses
        self._t_last_recovered: Optional[float] = None

    # ------------------------------------------------------------- routing
    def healthy(self) -> List[int]:
        return [ei for ei in range(len(self.engines))
                if ei not in self._quarantined]

    def route(self) -> int:
        """Replica index for the next admit: least pending among HEALTHY
        replicas, round-robin among ties (deterministic under the
        injectable clocks); -1 when the whole fleet is quarantined."""
        healthy = self.healthy()
        if not healthy:
            return -1
        loads = [self.engines[ei].pending() for ei in healthy]
        lo = min(loads)
        ties = [ei for ei, n in zip(healthy, loads) if n == lo]
        return ties[next(self._rr) % len(ties)]

    def submit(self, query, deadline: Optional[float] = None,
               arrival: Optional[float] = None) -> int:
        """Admit one request to the fleet; returns its GLOBAL rid.  With
        every replica quarantined the request responds ``failed`` (with
        a reason) instead of raising."""
        gid = next(self._rid)
        ei = self.route()
        if ei < 0:
            self._fail(gid, "all replicas quarantined at admission")
            return gid
        rid = self.engines[ei].submit(query, deadline=deadline,
                                      arrival=arrival)
        self._local[gid] = (ei, rid)
        self._gid[(ei, rid)] = gid
        return gid

    def _globalize(self, ei: int,
                   responses: List[EngineResponse]
                   ) -> List[EngineResponse]:
        out = []
        for r in responses:
            gid = self._gid.pop((ei, r.rid))
            del self._local[gid]
            n = self._attempts.pop(gid, 0)
            if n and r.status == "ok":        # served after failover
                r = dataclasses.replace(r, status="retried", retries=n)
                self._t_last_recovered = self.clock()
            out.append(dataclasses.replace(r, rid=gid))
        return out

    # ----------------------------------------------------- fault handling
    def _fail(self, gid: int, reason: str) -> None:
        self.failures += 1
        self._failed.append(EngineResponse(
            rid=gid, result=_void_result(), latency_s=0.0, queue_s=0.0,
            waves=0, stream_hit=False, deadline_met=None,
            status="failed", retries=self._attempts.pop(gid, 0),
            reason=reason))

    def _quarantine(self, ei: int, reason: str,
                    revivable: bool) -> List[EngineResponse]:
        """Evict a replica and fail its requests over: buffered (already
        computed) responses are kept, every un-responded request is
        resubmitted to a healthy replica with exponential backoff —
        each exactly once, under the bounded retry budget."""
        now = self.clock()
        self._quarantined[ei] = {"t": now, "reason": reason,
                                 "revivable": revivable}
        self.quarantine_log.append({"t": now, "replica": ei,
                                    "reason": reason,
                                    "revivable": revivable})
        self.monitor.evict([ei])
        record("router:quarantine")
        done, specs = self.engines[ei].evacuate()
        out = self._globalize(ei, done)
        for rid, query, deadline in specs:
            gid = self._gid.pop((ei, rid))
            del self._local[gid]
            n = self._attempts.get(gid, 0) + 1
            self._attempts[gid] = n
            if n > self.policy.retry_budget:
                self._fail(gid, f"retry budget ({self.policy.retry_budget})"
                                f" exhausted; last replica {ei}: {reason}")
                continue
            nei = self.route()
            if nei < 0:
                self._fail(gid, f"no healthy replica left "
                                f"(replica {ei}: {reason})")
                continue
            delay = self.policy.backoff_s * (2 ** (n - 1))
            nrid = self.engines[nei].submit(
                query, deadline=deadline, arrival=self.clock() + delay)
            self._local[gid] = (nei, nrid)
            self._gid[(nei, nrid)] = gid
            self.retries += 1
            record("router:retry")
        return out

    def _maybe_revive(self) -> None:
        now = self.clock()
        for ei in [ei for ei, q in self._quarantined.items()
                   if q["revivable"]
                   and now - q["t"] >= self.policy.revive_after_s]:
            eng = self.engines[ei]
            if eng.epoch_behind():
                # a commit landed while the replica sat in quarantine:
                # it MUST resync to the head epoch before readmission
                # (its request state was evacuated, so the cohort is
                # drained by construction)
                eng.resync()
                record("router:revive_resync")
            del self._quarantined[ei]
            self.monitor.restore(ei)
            self.quarantine_log.append({"t": now, "replica": ei,
                                        "reason": "revived",
                                        "revivable": True})

    # ------------------------------------------------------- epoch rollout
    def _grant_rollout(self) -> None:
        """Serialize the epoch rollout replica-by-replica (DESIGN.md
        §6.5): exactly ONE behind healthy replica holds the resync grant
        at a time, so the fleet never loses more than one replica's
        serving capacity to a rebuild.  Behind replicas without the
        grant keep draining their pinned in-flight cohort but admit no
        new joins (new admissions must see the committed epoch).  The
        grantee with a drained cohort resyncs HERE — it may have no
        pending work, in which case the step loop would never reach
        it."""
        behind = [ei for ei in self.healthy()
                  if self.engines[ei].epoch_behind()]
        lead = behind[0] if behind else -1
        for ei in self.healthy():
            self.engines[ei]._resync_allowed = (not behind) or ei == lead
        if lead >= 0 and not self.engines[lead]._inflight:
            self.engines[lead].resync()
            record("router:rollout")

    # --------------------------------------------------------------- drive
    def pending(self) -> int:
        """Requests admitted but not yet responded (wherever they live —
        a replica's lifecycle or the failed buffer)."""
        return len(self._local) + len(self._failed)

    def step(self) -> List[EngineResponse]:
        """One fleet step: every healthy replica with work steps once
        (its own continuous-batching wave) under the health plane;
        responses come back with global rids, failures as ``failed``
        responses."""
        self._maybe_revive()
        self._grant_rollout()
        out: List[EngineResponse] = []
        timeout = self.monitor.cfg.heartbeat_timeout
        for ei, eng in enumerate(self.engines):
            # _completed counts too: a failed-at-submit response (over-
            # load / validation) buffers without ever becoming pending,
            # and only a step() flushes it
            if ei in self._quarantined \
                    or not (eng.pending() or eng._completed):
                continue
            t0 = self.clock()
            try:
                resp = eng.step()
            except ReplicaCrash as e:
                out.extend(self._quarantine(ei, str(e), revivable=False))
                continue
            except TransientVerifierError as e:
                out.extend(self._quarantine(ei, str(e), revivable=True))
                continue
            out.extend(self._globalize(ei, resp))
            if self.clock() - t0 > timeout:
                # the step eventually returned, but past the heartbeat
                # timeout — a concurrent monitor would have declared the
                # replica dead mid-step; quarantine it (its just-emitted
                # responses above are valid and kept)
                out.extend(self._quarantine(
                    ei, f"hung step ({self.clock() - t0:.3f}s > "
                        f"heartbeat timeout {timeout}s)", revivable=True))
        for ei in self.monitor.stragglers():
            if ei not in self._quarantined:
                out.extend(self._quarantine(
                    ei, "straggler (step latency over "
                        f"{self.monitor.cfg.straggler_factor}x fleet "
                        "median)", revivable=True))
        out.extend(self._failed)
        self._failed = []
        return out

    def drain(self) -> List[EngineResponse]:
        """Step until every admitted request has responded (ok, shed,
        retried, or failed).  Idle gaps — backoff resubmissions or
        future-dated arrivals — sleep to the earliest arrival across the
        fleet; a quarantine cooldown sleeps in ``revive_after_s`` hops."""
        out: List[EngineResponse] = []
        while self.pending():
            n0 = len(out)
            for e in self.engines:    # so _last_wave reflects THIS pass
                e._last_wave = 0      # (skipped engines keep it stale)
            out.extend(self.step())
            if len(out) > n0 or any(e._last_wave for e in self.engines):
                continue                          # progress was made
            waits = [e._arrivals[0].arrival - self.clock()
                     for e in self.engines if e._arrivals]
            if any(e._inflight or e._queue for e in self.engines):
                continue                          # work ready next step
            if waits:
                self._sleep(max(min(waits), 0.0))
            elif self._quarantined:
                self._sleep(self.policy.revive_after_s)
            else:                                 # defensive: never spin
                self._sleep(0.001)
        for ei, eng in enumerate(self.engines):     # flush buffered
            if ei not in self._quarantined:
                out.extend(self._globalize(ei, eng.step()))
        return out

    def serve(self, queries: Sequence[np.ndarray],
              deadlines: Optional[Sequence[Optional[float]]] = None
              ) -> List[EngineResponse]:
        """Submit a batch across the fleet and drain it; responses in
        global request-id (= submission) order."""
        for i, q in enumerate(queries):
            self.submit(q, deadline=deadlines[i] if deadlines else None)
        return sorted(self.drain(), key=lambda r: r.rid)

    def warmup(self, sample: Sequence[np.ndarray],
               reset_counters: bool = True) -> None:
        """Warm every replica.  Compiled programs (waves, scans, solvers)
        are process-global, so replica 0 pays the compiles and the rest
        sweep compile-free — but each replica still primes its own
        stream cache and shape buckets."""
        for eng in self.engines:
            eng.warmup(sample, reset_counters=reset_counters)

    def summary(self) -> dict:
        """Fleet metrics: per-replica summaries + fleet totals, plus the
        health plane's failover accounting (DESIGN.md §6)."""
        from .instrument import _quantile

        per = [e.summary() for e in self.engines]
        lats = sorted(t.latency_s for e in self.engines
                      for t in e.counters.traces if t.status == "ok")
        return {
            "replicas": len(self.engines),
            "healthy_replicas": len(self.healthy()),
            "epoch": self.collection.epoch,
            "replica_epochs": [e.epoch for e in self.engines],
            "collection": self.collection.describe(),
            "requests": sum(p["requests"] for p in per),
            "shed": sum(p["shed"] for p in per),
            "retries": self.retries,
            "failed": self.failures,
            "quarantines": len([q for q in self.quarantine_log
                                if q["reason"] != "revived"]),
            "p50_latency_s": _quantile(lats, 0.50),
            "p99_latency_s": _quantile(lats, 0.99),
            "waves": sum(p["scheduler"]["waves"] for p in per),
            "per_replica": per,
        }
