"""KoiosSearch — end-to-end top-k semantic overlap search (paper Fig. 2).

Pipeline per (query x partition) tile:
    token stream (blocked sim matmul, one stacked sweep per request batch)
    ->  event expansion (inverted index)  ->  refinement (chunked
    vectorized filters)  ->  post-processing (No-EM + batched verification
    w/ Lemma-8 early termination).

All execution — single query, request batch, partitioned repository — is
one :class:`repro.core.scheduler.ExecutionPlan` driven by
:func:`repro.core.scheduler.run_plan`: ``search`` IS ``search_batch`` with
B=1.  The plan runs the request engine's per-partition waves — fused
device waves or host waves, as ``SearchParams.fused`` selects — with the
running theta_lb carried from partition to partition; both wave kinds
return bit-identical exact results (tests/test_scheduler.py).  On a device
mesh the bound exchange between partitions is an all-reduce-max over the
(pod, data) axes (``bound_exchange``; see
``repro.runtime.sharding.all_reduce_max`` and DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import numpy as np

from .inverted_index import InvertedIndex
from .scheduler import ExecutionPlan, SchedulerStats, run_plan
from .types import (SearchParams, SearchResult, SearchStats, SetCollection,
                    validate_query)


@dataclasses.dataclass
class KoiosIndex:
    """Prebuilt indexes for one partition of the repository."""

    coll: SetCollection
    inv: InvertedIndex
    id_offset: int = 0      # global id of the partition's first set

    @staticmethod
    def build(coll: SetCollection, id_offset: int = 0) -> "KoiosIndex":
        return KoiosIndex(coll=coll, inv=InvertedIndex.build(coll),
                          id_offset=id_offset)


def partition_ranges(set_sizes: np.ndarray, partitions: int,
                     by: str = "sets") -> np.ndarray:
    """Contiguous partition boundaries over the repository (paper §VI).

    ``by='sets'``: equal set counts (``np.linspace`` — the historical
    default).  ``by='tokens'``: greedy token-count balancer (DESIGN.md §9
    item 5, resolved): walk the prefix token counts and cut at whichever
    set boundary lands nearest each i/P share of the total, so every
    partition's token count is within half the largest set of the ideal
    share.  Balanced *work* per partition is what keeps fused waves
    uniform enough to overlap (LES3 makes the same observation for
    partition-quality -> exact-search cost).

    The token path NEVER emits an empty partition: when ``partitions >=
    num_sets`` it degenerates to one set per partition (``partitions``
    ranges cannot all be non-empty, so fewer bounds are returned rather
    than duplicated ones — an empty range would otherwise become a
    zero-row tile occupying a wave slot), and below that the forward +
    backward collision passes guarantee strictly increasing bounds even
    when one huge set drags every greedy cut to the same boundary."""
    n = len(set_sizes)
    if by == "sets":
        return np.linspace(0, n, partitions + 1).astype(int)
    assert by == "tokens", f"unknown partitioning {by!r}"
    if partitions >= n:
        # Degenerate split (partitions approaching/exceeding the set
        # count): the greedy balancer would collide every cut on the few
        # set boundaries available and the collision passes would clamp
        # into duplicated bounds — i.e. empty partitions.  One set per
        # partition is the only non-empty maximal split; callers see
        # len(bounds)-1 <= partitions ranges, all non-empty.
        return np.arange(n + 1, dtype=int)
    cum = np.concatenate([[0], np.cumsum(set_sizes, dtype=np.int64)])
    targets = cum[-1] * np.arange(1, partitions) / partitions
    cuts = np.searchsorted(cum, targets)
    # nearest set boundary to each target (greedy balance, then monotone)
    cuts = np.where(
        np.abs(cum[np.maximum(cuts - 1, 0)] - targets)
        <= np.abs(cum[np.minimum(cuts, n)] - targets),
        np.maximum(cuts - 1, 0), np.minimum(cuts, n))
    bounds = np.concatenate([[0], cuts, [n]]).astype(int)
    # non-empty partitions: the forward pass pushes collided cuts right
    # (clamped at n), the backward pass pulls the clamped tail left — a
    # single huge set can drag every greedy cut to n, and only the pair
    # of passes guarantees strictly increasing bounds for P < num_sets
    for i in range(1, len(bounds)):
        bounds[i] = min(max(bounds[i], bounds[i - 1] + 1), n)
    for i in range(len(bounds) - 2, 0, -1):
        bounds[i] = min(bounds[i], bounds[i + 1] - 1)
    assert np.all(np.diff(bounds) > 0), bounds   # every partition non-empty
    return bounds


def build_partition_indexes(coll: SetCollection, partitions: int,
                            by: str = "sets") -> "list[KoiosIndex]":
    """Build the per-partition indexes of a repository split — THE
    partitioning used by every serving entry point (``KoiosSearch`` and
    the request engine share it, so their plans decompose identically —
    a precondition of the engine == one-shot bit-identity).

    Since the collection became a first-class resource this is a thin
    wrapper over :meth:`repro.runtime.collection.ShardedCollection.build`:
    the returned indexes ARE that resource's :class:`Shard`s, so callers
    holding a bare index list still borrow (never own) device state."""
    from ..runtime.collection import ShardedCollection

    return ShardedCollection.build(coll, partitions, by=by).shards


_I32_MAX = np.int32(np.iinfo(np.int32).max)


@functools.lru_cache(maxsize=None)
def _merge_tree_fn(B_pad: int, P_pad: int, k: int):
    """Jitted device-side log-depth top-k merge tree for a static
    (B_pad, P_pad, k) geometry (pow2-padded: O(log) compiled variants).

    Each level pairs adjacent partitions' k-lists, sorts each 2k-row
    lexicographically ascending by (key, seq) with ``jax.lax.sort``
    (num_keys=2), and keeps the first k — the top-k of a union is the
    top-k of the unions' top-ks, so log2(P_pad) levels reproduce the
    global order exactly.  ``key = -(lb + 0.0)`` makes ascending-key
    order equal descending-lb order while canonicalizing -0.0 to +0.0
    (numpy's stable argsort treats the two zeros as equal ties broken by
    position; lax.sort's total order would otherwise put -0.0 first),
    and ``seq`` — the entry's position in the partition-order
    concatenation — breaks ties exactly like ``np.argsort(-lb,
    kind='stable')``.  Pads carry lb=-inf (key=+inf) and seq=INT32_MAX,
    so they sort after every real entry at every level."""
    import jax
    import jax.numpy as jnp

    def fn(lb, ub, ids, seq):
        key = jnp.negative(lb + jnp.float32(0.0))
        ops = (key, seq, lb, ub, ids)
        p = P_pad
        while p > 1:
            ops = tuple(x.reshape(B_pad, p // 2, 2 * k) for x in ops)
            ops = jax.lax.sort(ops, dimension=2, num_keys=2)
            ops = tuple(x[:, :, :k] for x in ops)
            p //= 2
        if P_pad == 1:           # no pairing level ran: sort the one list
            ops = jax.lax.sort(ops, dimension=2, num_keys=2)
        _, _, lb, ub, ids = (x.reshape(B_pad, k) for x in ops)
        return lb, ub, ids

    return jax.jit(fn)


def _merge_stats(results: Sequence[SearchResult]) -> SearchStats:
    """Host-side per-query stats fold (sums; theta_lb_final is a max)."""
    stats = SearchStats()
    for r in results:
        for f, v in r.stats.as_dict().items():
            setattr(stats, f, getattr(stats, f) + v if f != "theta_lb_final"
                    else max(getattr(stats, f), v))
    return stats


def merge_topk_batch(per_query: Sequence[Sequence[SearchResult]],
                     k: int) -> "list[SearchResult]":
    """Merge every query's per-partition top-k lists through ONE
    device-side log-depth reduction tree dispatch (paper:
    'merge-sorted'; DESIGN.md §5).

    Bit-identical to the historical host merge —
    ``np.argsort(-lb, kind='stable')[:k]`` over the partition-order
    concatenation — because the tree's (key, seq) total order IS that
    stable order (see :func:`_merge_tree_fn`); only each partition's
    first k entries enter the tree (a sorted partition list's k+1-th
    entry is preceded by k same-partition entries of >= lb and smaller
    seq, so it can never reach the global top-k).  Stats merge on host:
    they are O(P) scalars and schedule bookkeeping, not ranking state."""
    from ..runtime import instrument
    from .types import pow2

    B = len(per_query)
    if B == 0:
        return []
    P = max(len(rs) for rs in per_query)
    B_pad, P_pad = pow2(max(B, 1)), pow2(max(P, 1))
    lb = np.full((B_pad, P_pad, k), -np.inf, np.float32)
    ub = np.full((B_pad, P_pad, k), -np.inf, np.float32)
    ids = np.full((B_pad, P_pad, k), -1, np.int32)
    seq = np.full((B_pad, P_pad, k), _I32_MAX, np.int32)
    totals = np.zeros(B, np.int64)
    for qi, rs in enumerate(per_query):
        off = 0
        for pi, r in enumerate(rs):
            m = min(len(r.lb), k)
            lb[qi, pi, :m] = r.lb[:m]
            ub[qi, pi, :m] = r.ub[:m]
            ids[qi, pi, :m] = r.ids[:m]
            seq[qi, pi, :m] = off + np.arange(m)
            off += len(r.lb)     # seq keeps FULL concatenation positions
        totals[qi] = off
    instrument.record("h2d:topk_merge")
    m_lb, m_ub, m_ids = _merge_tree_fn(B_pad, P_pad, k)(lb, ub, ids, seq)
    instrument.record("d2h:topk_merge")
    m_lb, m_ub, m_ids = (np.asarray(x) for x in (m_lb, m_ub, m_ids))
    out = []
    for qi, rs in enumerate(per_query):
        n = int(min(k, totals[qi]))
        out.append(SearchResult(
            ids=m_ids[qi, :n], lb=m_lb[qi, :n], ub=m_ub[qi, :n],
            stats=_merge_stats(rs)))
    return out


def merge_topk(results: Sequence[SearchResult], k: int) -> SearchResult:
    """Merge one query's per-partition top-k lists — the B=1 case of
    :func:`merge_topk_batch` (same device reduction tree)."""
    return merge_topk_batch([results], k)[0]


class KoiosSearch:
    """Public search API over a (possibly partitioned) repository.

    ``params.fused`` selects the wave kind (``core.wave.fused_available``):
    'auto' (default) runs fused device waves on a TPU and host waves
    elsewhere, 'interpret' runs fused waves on any backend, 'off' runs
    host waves; all are exact and bit-identical.  ``partition_by`` picks
    the repository split: 'sets' (equal set counts) or 'tokens' (greedy
    token-count balance — see :func:`partition_ranges`).
    ``bound_exchange`` optionally plugs a mesh all-reduce-max into the
    theta_lb exchange between partitions (see
    ``repro.runtime.sharding.all_reduce_max``).  ``scheduler_stats``
    holds the :class:`SchedulerStats` of the most recent call.
    ``stream_cache`` optionally plugs a
    :class:`~repro.core.token_stream.TokenStreamCache` into the one-shot
    path: repeated queries skip the blocked stream sweep (bit-identical
    streams, DESIGN.md §3.2) — the request engine's cache layer,
    available without the engine.

    Collection state lives in a
    :class:`~repro.runtime.collection.ShardedCollection` resource, NOT
    here: pass ``collection=`` to serve an existing (possibly placed)
    resource — sharing its device-resident operands with every other
    consumer — or let the constructor build a private one from ``coll``
    (``partitions``/``partition_by`` become the shard split).  Either
    way ``KoiosSearch`` only borrows per-shard operand views; it owns no
    device arrays, so N search objects over one resource pay for one
    upload of everything (DESIGN.md §5).
    """

    def __init__(self, coll: Optional[SetCollection], sim_provider,
                 params: Optional[SearchParams] = None,
                 partitions: int = 1,
                 bound_exchange: Optional[Callable] = None,
                 partition_by: str = "sets",
                 stream_cache=None, collection=None):
        from ..runtime.collection import ShardedCollection

        self.params = params or SearchParams()
        self.sim = sim_provider
        if collection is None:
            collection = ShardedCollection.build(coll, partitions,
                                                 by=partition_by)
        self.collection = collection
        self.bound_exchange = bound_exchange
        self.stream_cache = stream_cache
        self.scheduler_stats: Optional[SchedulerStats] = None

    # head-epoch delegation (DESIGN.md §6.5): a one-shot search always
    # sees the latest committed repository; each search_batch call pins
    # the head for its own duration so a concurrent commit cannot tear it
    @property
    def coll(self) -> SetCollection:
        return self.collection.coll

    @property
    def partitions(self):
        return self.collection.shards

    def search(self, query: np.ndarray,
               k: Optional[int] = None) -> SearchResult:
        """Single-query search == ``search_batch`` with B=1."""
        return self.search_batch([query], k=k)[0]

    def search_batch(self, queries: Sequence[np.ndarray],
                     k: Optional[int] = None) -> "list[SearchResult]":
        """Search B queries — one execution plan, every (query x
        partition) tile through the shared pipeline.

        Results are exact and independent of the wave kind and of the
        batch composition: ``search_batch(qs)[i]`` is bit-identical to
        ``search(qs[i])`` (same ids, same lb/ub floats, and on host waves
        the same per-phase statistics).
        """
        params = self.params if k is None else dataclasses.replace(
            self.params, k=k)
        queries = [validate_query(q, self.sim) for q in queries]
        if not queries:
            return []
        # pin the head epoch for the call: the whole plan computes
        # against one consistent snapshot even if a live-update commit
        # lands mid-search (the one-shot counterpart of the engine's
        # admission pinning, DESIGN.md §6.5)
        epoch = self.collection.pin()
        try:
            streams = None
            if self.stream_cache is not None:
                from .token_stream import build_token_stream_batch_cached
                self.stream_cache.set_epoch(epoch.epoch)
                streams = build_token_stream_batch_cached(
                    queries, self.sim, params.alpha, self.stream_cache,
                    use_kernel=params.stream_use_kernel,
                    interpret=params.interpret)
            plan = ExecutionPlan(epoch.shards, queries,
                                 pool_coll=epoch.coll, epoch=epoch.epoch)
            per_query = run_plan(plan, self.sim, params,
                                 bound_exchange=self.bound_exchange,
                                 streams=streams)
            self.scheduler_stats = plan.stats
        finally:
            self.collection.release(epoch)
        # ONE device dispatch merges every query's per-shard top-k lists
        # through the log-depth reduction tree (bit-identical to the
        # historical host concatenation merge — see merge_topk_batch)
        return merge_topk_batch(per_query, params.k)
