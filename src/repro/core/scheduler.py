"""Partition-scheduled KOIOS execution engine (paper §VI scale-out).

Every search request — single query, request batch, partitioned repository,
or all three — is one :class:`ExecutionPlan`: a set of (query x partition)
*tiles* driven through one shared pipeline.  The scheduler replaces the
historical trio of hand-rolled loops (per-query search, per-partition host
loop, per-partition batched search) with a single code path:

  overlap (default)
      All tiles' refinement scans are dispatched before any is
      materialized (JAX dispatch is async: partition p+1's scan executes
      on-device while the host expands events for and materializes
      earlier tiles, with no host round-trip between partitions — the
      sequential loop instead stalls every partition's refinement behind
      the previous partition's full post-processing), every tile's
      verification requests drain through ONE cross-partition/cross-query
      :class:`VerifierPool` queue (fewer, fuller solver calls), and
      theta_lb feedback is *bidirectional*: a bound raised by any tile's
      verification round immediately re-prunes still-queued candidates of
      every other tile of the same query — including tiles of *earlier*
      partitions, which the sequential running-max loop could never reach.
      On a device mesh the per-round bound exchange is an all-reduce-max
      over the (pod, data) axes (``bound_exchange`` hook; see
      ``repro.runtime.sharding.all_reduce_max`` and DESIGN.md §5).

  sequential
      The pre-scheduler reference trajectory: partitions run one after the
      other, later partitions inheriting the running max of earlier
      partitions' final k-th scores.  Kept (cheaply — it is the same tile
      machinery with a different drive order) as the bit-identical
      baseline for tests and the A/B arm of
      ``benchmarks/response_time.py --partitions N --overlap``.

Both schedules return exact top-k results; tests assert they are
bit-identical on every (partitions x batch x verifier) combination.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import numpy as np

from .postprocess import PostprocessState, VerifierPool, drive_states
from .refinement import _dispatch_refinement, _materialize_refinement
from .token_stream import build_token_stream_batch, expand_to_events
from .types import (SearchParams, SearchResult, SearchStats, SetCollection)
from ..runtime import instrument
from ..runtime.instrument import span


def _build_streams(plan: "ExecutionPlan", sim, params: SearchParams,
                   streams) -> list:
    """Plan-wide per-query streams: the precomputed list when the caller
    (request engine / stream-cache-aware search) supplies one, else one
    stacked batch build — construction is split from execution so streams
    can come from the LRU cache (DESIGN.md §3.2)."""
    if streams is not None:
        assert len(streams) == len(plan.queries)
        return streams
    return build_token_stream_batch(plan.queries, sim, params.alpha,
                                    use_kernel=params.stream_use_kernel,
                                    interpret=params.interpret)


@dataclasses.dataclass
class SchedulerStats:
    """Instrumentation of one plan execution (the overlap/fused story)."""

    tiles: int = 0                 # (query x partition) tiles executed
    rounds: int = 0                # host lock-step verification rounds
    fused_requests: int = 0        # verify requests fused across tiles
    bound_raises: int = 0          # tile thetas raised by another tile
    backward_raises: int = 0       # ... where the source is a LATER partition
    schedule: str = ""             # resolved drive order of this plan
    waves: int = 0                 # waves executed (fused device programs
    #                                or the engine's host wave steps)
    device_rounds: int = 0         # verification rounds run inside waves
    theta_trace: List[np.ndarray] = dataclasses.field(default_factory=list)
    # per-query theta_lb after each round (monotone non-decreasing rows)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["theta_trace"] = [t.tolist() for t in self.theta_trace]
        return d


@dataclasses.dataclass
class _Tile:
    """One (query, partition) unit of work."""

    qi: int                        # query index within the plan
    pi: int                        # partition index within the plan
    index: "object"                # KoiosIndex of the partition
    id_base: int                   # added to candidate ids in pool requests
    events: Optional[object] = None
    launched: Optional[tuple] = None      # async refinement handle
    ref: Optional[object] = None
    state: Optional[PostprocessState] = None
    result: Optional[SearchResult] = None


def _empty_result() -> SearchResult:
    return SearchResult(
        ids=np.zeros(0, np.int32), lb=np.zeros(0, np.float32),
        ub=np.zeros(0, np.float32), stats=SearchStats())


class ExecutionPlan:
    """A request batch decomposed into (query x partition) tiles.

    ``pool_coll`` is the collection the shared verifier resolves candidate
    ids against; ``request_id_bases[pi]`` translates partition-local ids
    into that collection's id space (the partition's global offset when
    ``pool_coll`` is the full repository, 0 when it is the partition
    itself).
    """

    # Plan-query ring bounds (DESIGN.md §9 item 9): once more than
    # ``compact_threshold`` of a plan's query slots are retired
    # tombstones (and the list is at least ``compact_min`` long),
    # ``retire_tiles`` compacts the append-only list in place — a
    # weeks-long engine's plan stays proportional to its LIVE requests
    # instead of growing with every request ever served.
    compact_threshold: float = 0.5
    compact_min: int = 64

    def __init__(self, indexes: Sequence, queries: Sequence[np.ndarray],
                 pool_coll: SetCollection,
                 theta0: Optional[Sequence[float]] = None,
                 request_id_bases: Optional[Sequence[int]] = None,
                 epoch: int = 0):
        # a ShardedCollection resource is a valid tile source: its shards
        # ARE the plan's per-partition indexes (borrowed, never copied)
        if hasattr(indexes, "shards"):
            indexes = indexes.shards
        # audit tag (DESIGN.md §6.5): the collection epoch this plan's
        # tiles compute against — a plan NEVER migrates epochs; engines
        # rebuild the plan on resync
        self.epoch = int(epoch)
        self.indexes = list(indexes)
        self.queries = [np.asarray(q, dtype=np.int32) for q in queries]
        self.pool_coll = pool_coll
        self.theta0 = np.asarray(
            theta0 if theta0 is not None else [0.0] * len(self.queries),
            np.float64)
        bases = (request_id_bases if request_id_bases is not None
                 else [ix.id_offset for ix in self.indexes])
        self._bases = [int(b) for b in bases]
        self.tiles = [
            _Tile(qi=qi, pi=pi, index=index, id_base=self._bases[pi])
            for pi, index in enumerate(self.indexes)
            for qi in range(len(self.queries))]
        self.stats = SchedulerStats(tiles=len(self.tiles))

    # ------------------------------------------------------------- helpers
    def add_queries(self, queries: Sequence[np.ndarray],
                    theta0: Optional[Sequence[float]] = None
                    ) -> "tuple[range, List[_Tile]]":
        """Absorb late-arriving queries into the plan (continuous
        batching, DESIGN.md §3.2): appends the queries plus one tile per
        partition each, and returns their query-index range and the new
        tiles.  Sound mid-flight: a query's tiles only ever read its own
        theta entry, and row-level numerics are schedule-invariant, so
        joining between waves cannot perturb any in-flight query."""
        queries = [np.asarray(q, dtype=np.int32) for q in queries]
        lo = len(self.queries)
        self.queries.extend(queries)
        extra = np.asarray(
            theta0 if theta0 is not None else [0.0] * len(queries),
            np.float64)
        assert len(extra) == len(queries)
        self.theta0 = np.concatenate([self.theta0, extra])
        new = [_Tile(qi=qi, pi=pi, index=index, id_base=self._bases[pi])
               for pi, index in enumerate(self.indexes)
               for qi in range(lo, len(self.queries))]
        self.tiles.extend(new)
        self.stats.tiles = len(self.tiles)
        return range(lo, len(self.queries)), new

    def retire_tiles(self, qis) -> "Optional[dict]":
        """Drop responded queries' tiles (and query arrays) so a
        long-running engine plan does not accumulate finished work;
        their queries-list slots are tombstoned, and once tombstones
        exceed ``compact_threshold`` of a ``compact_min``-sized list the
        list is compacted in place (the bounded ring, DESIGN.md §9 item
        9).  Returns the {old_qi: new_qi} remap when a compaction
        happened (callers holding qi-indexed state — the request engine
        — must apply it), else None."""
        gone = set(int(qi) for qi in qis)
        self.tiles = [t for t in self.tiles if t.qi not in gone]
        for qi in gone:
            self.queries[qi] = None
        retired = sum(1 for q in self.queries if q is None)
        if (len(self.queries) < self.compact_min
                or retired <= self.compact_threshold * len(self.queries)):
            return None
        live = [qi for qi, q in enumerate(self.queries) if q is not None]
        remap = {old: new for new, old in enumerate(live)}
        self.queries = [self.queries[old] for old in live]
        self.theta0 = self.theta0[live]
        for t in self.tiles:
            t.qi = remap[t.qi]
        return remap

    def results(self) -> List[List[SearchResult]]:
        """Per-query, per-partition (partition-ascending) local results."""
        out: List[List[SearchResult]] = [[] for _ in self.queries]
        for t in sorted(self.tiles, key=lambda t: (t.qi, t.pi)):
            out[t.qi].append(t.result)
        return out


def _launch_tile(tile: _Tile, stream, query, params: SearchParams) -> None:
    """Expand the (partition-independent) stream through the tile's
    inverted index and dispatch its refinement scan asynchronously."""
    coll = tile.index.coll
    events = expand_to_events(stream, tile.index.inv)
    if len(events) == 0:
        tile.result = _empty_result()
        return
    tile.events = events
    tile.launched = _dispatch_refinement(
        events, coll.set_sizes, len(query), coll.total_tokens,
        params.k, params.alpha, params.chunk_size, params.ub_mode,
        layout=params.refine_layout)


def _materialize_tile(tile: _Tile) -> None:
    out, n_chunks = tile.launched
    tile.launched = None
    tile.ref = _materialize_refinement(out, n_chunks, tile.events)
    tile.events = None          # free the expanded postings (P x B tiles)


def _make_state(tile: _Tile, query, theta0: float,
                params: SearchParams) -> None:
    ref = tile.ref
    ref.theta_lb = max(ref.theta_lb, float(theta0))
    surv = (ref.seen & ref.alive).nonzero()[0]
    tile.state = PostprocessState(
        query, surv, ref.S[surv], ref.ub[surv], ref.theta_lb, params,
        ref.stats, id_base=tile.id_base)
    tile.ref = None             # survivors are copied into the state


def _finish_tile(tile: _Tile, id_offset: int) -> None:
    r = tile.state.result()
    tile.result = SearchResult(
        ids=(r.ids + id_offset).astype(np.int32),
        lb=r.lb, ub=r.ub, stats=r.stats)
    st = r.stats
    instrument.record("filter:candidates", st.candidates)
    instrument.record("filter:pruned_refinement", st.pruned_refinement)
    instrument.record("filter:pruned_postprocess", st.pruned_postprocess)
    instrument.record("filter:no_em", st.pruned_no_em)
    instrument.record("filter:em_early", st.pruned_em_early)
    instrument.record("filter:em_full", st.exact_matches)


def run_plan(plan: ExecutionPlan, sim_provider, params: SearchParams,
             schedule: str = "overlap",
             bound_exchange: Optional[Callable] = None,
             streams=None) -> List[List[SearchResult]]:
    """Drive every tile of ``plan`` to completion; returns per-query lists
    of per-partition results (partition order), ids already globalized.

    ``schedule='fused'`` runs the on-device wave pipeline on a TPU
    backend (or anywhere with ``params.fused == 'interpret'``) and
    resolves to ``overlap`` for ``fused='off'`` or ``'auto'`` off-TPU;
    a fused request the wave cannot serve raises (see
    ``core.wave.fused_available``).  All three schedules return
    bit-identical exact results.  ``streams`` optionally
    supplies precomputed per-query token streams (the stream-cache path,
    DESIGN.md §3.2) instead of building them here."""
    if schedule == "fused":
        from .wave import fused_available
        if not fused_available(params, sim_provider):
            schedule = "overlap"
    plan.stats.schedule = schedule
    if schedule == "fused":
        _run_fused(plan, sim_provider, params, bound_exchange,
                   streams=streams)
    elif schedule == "overlap":
        _run_overlapped(plan, sim_provider, params, bound_exchange,
                        streams=streams)
    elif schedule == "sequential":
        _run_sequential(plan, sim_provider, params, bound_exchange,
                        streams=streams)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    return plan.results()


# --------------------------------------------------------------- wave step
def run_wave(plan: ExecutionPlan, tiles: Sequence[_Tile], streams,
             theta, pool: VerifierPool, params: SearchParams) -> None:
    """Execute one host *wave* — any subset of the plan's tiles, mixing
    queries AND partitions — to completion, then fold each finished
    tile's k-th score back into its query's ``theta`` carry (in place).

    This is plan execution split from plan construction: the request
    engine (``runtime.engine``) calls it with whatever tile cohort the
    admission queue coalesced for this step (a tile per live request,
    each at its own next partition — continuous batching), while
    ``_run_sequential`` drives one partition's tiles per wave.  Within
    the wave, refinement dispatch is pipelined across all tiles and
    verification drains through the shared ``pool`` queue — the overlap
    machinery at wave granularity.
    """
    plan.stats.waves += 1
    for t in tiles:
        _launch_tile(t, streams[t.qi], plan.queries[t.qi], params)
    live = [t for t in tiles if t.result is None]
    for t in live:
        _materialize_tile(t)
        _make_state(t, plan.queries[t.qi], theta[t.qi], params)
    drive_states(pool, [t.state for t in live],
                 round_hook=lambda n: _count_round(plan, n))
    for t in live:
        _finish_tile(t, t.index.id_offset)
    for t in tiles:
        if len(t.result.lb) >= params.k:
            theta[t.qi] = max(theta[t.qi],
                              float(t.result.lb[params.k - 1]))


# --------------------------------------------------------------- sequential
def _run_sequential(plan: ExecutionPlan, sim, params: SearchParams,
                    bound_exchange: Optional[Callable] = None,
                    streams=None) -> None:
    """Partitions one after the other, sharing the running max of final
    k-th scores — the paper's host reference loop (and the historical
    ``search``/``search_batch`` trajectory, bit for bit): one
    :func:`run_wave` per partition.  The bound exchange (when
    configured) runs once per completed partition, at the loop's single
    inter-partition communication point."""
    streams = _build_streams(plan, sim, params, streams)
    pool = VerifierPool(plan.pool_coll, sim, params)
    theta = plan.theta0.copy()
    for pi in range(len(plan.indexes)):
        run_wave(plan, [t for t in plan.tiles if t.pi == pi], streams,
                 theta, pool, params)
        if pi < len(plan.indexes) - 1:      # no consumer after the last
            theta = _exchange(theta, bound_exchange)


# ------------------------------------------------------------------ overlap
def _run_overlapped(plan: ExecutionPlan, sim, params: SearchParams,
                    bound_exchange: Optional[Callable],
                    streams=None) -> None:
    """All tiles in flight at once: pipelined refinement dispatch across
    partitions, one global verification queue, bidirectional bounds."""
    streams = _build_streams(plan, sim, params, streams)
    # Dispatch EVERY tile's refinement before materializing any: the
    # device works through later partitions' scans back-to-back while the
    # host expands and materializes earlier tiles (the sequential loop
    # instead parks each partition's refinement behind the previous
    # partition's full post-processing).
    for t in plan.tiles:
        _launch_tile(t, streams[t.qi], plan.queries[t.qi], params)
    live = [t for t in plan.tiles if t.result is None]
    for t in live:
        _materialize_tile(t)

    # Initial bound exchange: every tile starts from the best refinement
    # bound of ANY of its query's tiles (each partition's k-th greedy score
    # lower-bounds the global k-th SO), not just its own.
    theta = plan.theta0.copy()
    _exchange_bounds(plan, live, theta, bound_exchange,
                     tile_theta=lambda t: t.ref.theta_lb,
                     raisable=lambda t: True)
    for t in live:
        _make_state(t, plan.queries[t.qi], theta[t.qi], params)

    pool = VerifierPool(plan.pool_coll, sim, params)
    drive_states(pool, [t.state for t in live],
                 round_hook=lambda n: _feedback_round(plan, live, theta,
                                                      bound_exchange, n))
    for t in live:
        _finish_tile(t, t.index.id_offset)


# --------------------------------------------------------------------- fused
def _wave_tile_state(tile: _Tile, row: int, launch, out, query,
                     theta_q: float, params: SearchParams) -> bool:
    """Resume one tile from a materialized wave's row: build its
    ``PostprocessState`` via ``PostprocessState.from_wave`` (or mark the
    tile empty).  Returns whether the tile is live.  Shared by the
    all-partitions fused drive and the engine's single-wave step."""
    meta = launch.tile_meta[row]
    if meta.empty:
        tile.result = _empty_result()
        return False
    surv = out.surv_idx[row][:int(out.surv_cnt[row])]
    stats = SearchStats(
        candidates=int(out.candidates[row]),
        pruned_refinement=int(out.pruned_ref[row]),
        pruned_postprocess=int(out.pruned_post[row]),
        stream_tuples=meta.n_tuples,
        stream_events=meta.n_events,
        refinement_chunks=meta.n_chunks)
    tile.state = PostprocessState.from_wave(
        query, surv,
        out.lb[row][surv], out.ub[row][surv],
        out.live[row][surv], out.verified[row][surv],
        em_early=int(out.em_early[row]),
        em_full=int(out.em_full[row]),
        theta_lb=float(theta_q), params=params, stats=stats,
        id_base=tile.id_base)
    return True


def run_fused_wave(plan: ExecutionPlan, tiles: Sequence[_Tile], streams,
                   theta, pool: VerifierPool, params: SearchParams,
                   runner) -> None:
    """Execute one fused *device* wave for a tile cohort sharing a single
    partition (the engine's continuous-batching step, device edition):
    dispatch the wave program over the cohort's queries, resume each tile
    through ``PostprocessState.from_wave``, drain the host continuation
    through the shared ``pool``, and fold finished k-th scores back into
    the per-query ``theta`` carries.  ``runner`` is an engine-lifetime
    :class:`core.wave.WaveRunner` (see ``wave.wave_runner_for``), so the
    normalized table and per-partition dense operands are reused across
    requests."""
    from .wave import _pow2

    assert len({t.pi for t in tiles}) == 1, "one partition per fused wave"
    index = tiles[0].index
    queries = [plan.queries[t.qi] for t in tiles]
    wave_streams = [streams[t.qi] for t in tiles]
    theta0 = np.asarray([theta[t.qi] for t in tiles], np.float64)
    with span("koios.wave.launch"):
        theta_dev = runner.init_theta(theta0, _pow2(max(1, len(queries))))
        launch, theta_dev = runner.launch_wave(index, queries, wave_streams,
                                               theta_dev)
    plan.stats.waves += 1
    plan.stats.device_rounds += launch.cfg.rounds
    with span("koios.device_wait", what="wave"):
        out = runner.materialize(launch)
        instrument.record("d2h:theta_materialize")
        theta_out = np.maximum(theta0, np.asarray(theta_dev,
                                                  np.float64)[:len(queries)])
    live = []
    with span("koios.resume"):
        for row, t in enumerate(tiles):
            # theta carries fold the on-device exchange back in (monotone)
            theta[t.qi] = max(theta[t.qi], float(theta_out[row]))
            if _wave_tile_state(t, row, launch, out, plan.queries[t.qi],
                                theta_out[row], params):
                live.append(t)
    drive_states(pool, [t.state for t in live],
                 round_hook=lambda n: _count_round(plan, n))
    with span("koios.finish"):
        for t in live:
            _finish_tile(t, t.index.id_offset)
        for t in tiles:
            if len(t.result.lb) >= params.k:
                theta[t.qi] = max(theta[t.qi],
                                  float(t.result.lb[params.k - 1]))


def _run_fused(plan: ExecutionPlan, sim, params: SearchParams,
               bound_exchange: Optional[Callable],
               streams=None) -> None:
    """On-device wave pipeline (DESIGN.md §3): one device program per
    partition wave — refinement chunk scans, candidate compaction,
    theta_lb exchange, and the first R verification rounds — with waves
    chained through a donated on-device theta carry (no host round-trip
    between partitions).  The host drive loop resumes from each tile's
    wave state for the remaining verification, with the same global queue
    and bidirectional bound feedback as the overlap schedule."""
    from .wave import _pow2, wave_runner_for

    streams = _build_streams(plan, sim, params, streams)
    runner = wave_runner_for(sim, params)
    B_pad = _pow2(max(1, len(plan.queries)))
    theta_dev = runner.init_theta(plan.theta0, B_pad)
    # ONE host->device payload for the whole plan: the compact stream
    # tuples (partition-independent) — each wave expands them in-trace
    # through its partition's device-resident index (DESIGN.md §3.3)
    stream_ops = runner.stream_operands(plan.queries, streams, B_pad)

    # Dispatch EVERY wave before materializing any (the overlap idea, one
    # level up): wave p+1's program queues behind wave p on-device while
    # the host sizes and dispatches later partitions' waves.
    launches = []
    for index in plan.indexes:
        launch, theta_dev = runner.launch_wave(index, plan.queries,
                                               streams, theta_dev,
                                               stream_ops=stream_ops)
        launches.append(launch)
        plan.stats.waves += 1
        plan.stats.device_rounds += launch.cfg.rounds

    instrument.record("d2h:theta_materialize")
    theta = np.maximum(plan.theta0,
                       np.asarray(theta_dev,
                                  np.float64)[:len(plan.queries)])
    plan.stats.theta_trace.append(theta.copy())

    live: List[_Tile] = []
    for pi, launch in enumerate(launches):
        out = runner.materialize(launch)
        for t in (t for t in plan.tiles if t.pi == pi):
            if _wave_tile_state(t, t.qi, launch, out,
                                plan.queries[t.qi], theta[t.qi], params):
                live.append(t)

    # host continuation: same exchange + global queue as overlap
    _exchange_bounds(plan, live, theta, bound_exchange,
                     tile_theta=lambda t: t.state.theta_lb,
                     raisable=lambda t: not t.state.finished())
    for t in live:
        if not t.state.finished():
            t.state.raise_theta(theta[t.qi])
    pool = VerifierPool(plan.pool_coll, sim, params)
    drive_states(pool, [t.state for t in live],
                 round_hook=lambda n: _feedback_round(plan, live, theta,
                                                      bound_exchange, n))
    for t in live:
        _finish_tile(t, t.index.id_offset)


def _count_round(plan: ExecutionPlan, n_active: int) -> None:
    plan.stats.rounds += 1
    plan.stats.fused_requests += n_active


def _feedback_round(plan: ExecutionPlan, tiles, theta: np.ndarray,
                    bound_exchange: Optional[Callable],
                    n_active: int) -> None:
    """After each lock-step verification round: gather every tile's bound,
    all-reduce across tiles (and the mesh, when configured), and push the
    result back into every still-running tile — including tiles of earlier
    partitions, whose queued candidates are re-pruned on their next step."""
    _count_round(plan, n_active)
    _exchange_bounds(plan, tiles, theta, bound_exchange,
                     tile_theta=lambda t: t.state.theta_lb,
                     raisable=lambda t: not t.state.finished())
    for t in tiles:
        if not t.state.finished():
            t.state.raise_theta(theta[t.qi])    # no-op unless higher


def _exchange_bounds(plan: ExecutionPlan, tiles, theta: np.ndarray,
                     bound_exchange: Optional[Callable],
                     tile_theta: Callable, raisable: Callable) -> None:
    """One exchange point: fold every tile's bound into the per-query
    ``theta`` vector (in place), all-reduce it, and account raises —
    ``bound_raises`` for each raisable tile whose own bound is below the
    exchanged one, ``backward_raises`` when the improving tile sits in a
    LATER partition than the raised one.  Both overlap exchange points
    (refinement-time and per verification round) share this accounting."""
    source_pi = {}
    for t in tiles:
        v = tile_theta(t)
        if v > theta[t.qi]:
            theta[t.qi] = v
            source_pi[t.qi] = t.pi
    new_theta = _exchange(theta, bound_exchange)
    for t in tiles:
        if raisable(t) and new_theta[t.qi] > tile_theta(t):
            plan.stats.bound_raises += 1
            if source_pi.get(t.qi, t.pi) > t.pi:
                plan.stats.backward_raises += 1
    theta[:] = new_theta
    plan.stats.theta_trace.append(theta.copy())


def _exchange(theta: np.ndarray,
              bound_exchange: Optional[Callable]) -> np.ndarray:
    if bound_exchange is None:
        return theta
    # max with the local bounds: the exchange may narrow dtypes (rounding
    # toward -inf to stay certified), and theta must never decrease
    return np.maximum(theta,
                      np.asarray(bound_exchange(theta), np.float64))
