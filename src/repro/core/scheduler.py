"""Partition-scheduled KOIOS execution (paper §VI scale-out).

Every search request — single query, request batch, partitioned repository,
or all three — is one :class:`ExecutionPlan`: a set of (query x partition)
*tiles*.  A plan is driven by *waves*, each running a cohort of tiles to
completion, and there are two kinds of wave:

  host wave (:func:`run_wave`)
      Refinement dispatched for every tile of the cohort, then the
      verification of all of them drained through one shared
      :class:`VerifierPool` queue.
  fused wave (:func:`run_fused_wave`)
      One device program per partition cohort (refinement, compaction and
      the first verification rounds, ``core.wave``), then the same host
      continuation.

``SearchParams.fused`` picks the kind (``core.wave.fused_available``):
fused on a TPU or with ``'interpret'``, host waves otherwise.  The request
engine (``runtime.engine``) runs whatever cohort its admission queue
coalesced; the one-shot :func:`run_plan` runs one wave per partition over
every query, carrying each query's theta_lb from partition to partition.
Both kinds return the same exact top-k, bit for bit.
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
    """Instrumentation of one plan execution."""

    tiles: int = 0                 # (query x partition) tiles executed
    rounds: int = 0                # host lock-step verification rounds
    fused_requests: int = 0        # verify requests fused across tiles
    schedule: str = ""             # wave kind run_plan ran: fused | wave
    waves: int = 0                 # waves executed (fused device programs
    #                                or host waves)
    device_rounds: int = 0         # verification rounds run inside waves
    theta_trace: List[np.ndarray] = dataclasses.field(default_factory=list)
    # per-query theta_lb after each partition wave of run_plan (monotone
    # non-decreasing rows)

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
             bound_exchange: Optional[Callable] = None,
             streams=None) -> List[List[SearchResult]]:
    """Drive every tile of ``plan`` to completion; returns per-query lists
    of per-partition results (partition order), ids already globalized.

    One wave per partition, in partition order, over every query of the
    plan: :func:`run_fused_wave` where ``core.wave.fused_available``
    says the wave program runs, else :func:`run_wave`.  Each query's
    theta_lb is carried on the host from partition to partition, through
    ``bound_exchange`` (a mesh all-reduce-max, when configured) after
    every partition but the last.  A fused request the wave cannot serve
    raises.  ``streams`` optionally supplies precomputed per-query token
    streams (the stream-cache path, DESIGN.md §3.2) instead of building
    them here."""
    from .wave import fused_available, wave_runner_for

    fused = fused_available(params, sim_provider)
    streams = _build_streams(plan, sim_provider, params, streams)
    pool = VerifierPool(plan.pool_coll, sim_provider, params)
    runner = wave_runner_for(sim_provider, params) if fused else None
    plan.stats.schedule = "fused" if fused else "wave"
    theta = plan.theta0.copy()
    for pi in range(len(plan.indexes)):
        tiles = [t for t in plan.tiles if t.pi == pi]
        if runner is not None:
            run_fused_wave(plan, tiles, streams, theta, pool, params, runner)
        else:
            run_wave(plan, tiles, streams, theta, pool, params)
        if pi < len(plan.indexes) - 1:      # no consumer after the last
            theta = _exchange(theta, bound_exchange)
        plan.stats.theta_trace.append(theta.copy())
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
    :func:`run_plan` drives one partition's tiles per wave.  Within the
    wave, refinement dispatch is pipelined across all tiles and
    verification drains through the shared ``pool`` queue.
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


# --------------------------------------------------------------------- fused
def _wave_tile_state(tile: _Tile, row: int, launch, out, query,
                     theta_q: float, params: SearchParams) -> bool:
    """Resume one tile from a materialized wave's row: build its
    ``PostprocessState`` via ``PostprocessState.from_wave`` (or mark the
    tile empty).  Returns whether the tile is live."""
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


def _count_round(plan: ExecutionPlan, n_active: int) -> None:
    plan.stats.rounds += 1
    plan.stats.fused_requests += n_active


def _exchange(theta: np.ndarray,
              bound_exchange: Optional[Callable]) -> np.ndarray:
    if bound_exchange is None:
        return theta
    # max with the local bounds: the exchange may narrow dtypes (rounding
    # toward -inf to stay certified), and theta must never decrease
    return np.maximum(theta,
                      np.asarray(bound_exchange(theta), np.float64))
