"""The token stream I_e — chunked, blocked-matmul replacement for Faiss+PQ.

Paper §IV: I_e yields (q, t, sim(q, t)) tuples for every vocabulary token t
with sim >= alpha to some query element, in globally descending similarity
order, realised with a Faiss index plus a |Q|-slot priority queue.

TPU adaptation (DESIGN.md §2): the index probe is a blocked similarity
matmul (MXU) over vocabulary tiles — `repro.kernels.cosine_topk` is the
fused Pallas kernel for the serving path; here the same block computation
runs through the jnp provider and the >=alpha entries are compacted host
side (compaction is inherently dynamic-shape, i.e. host work in either
implementation — the paper also walks its priority queue on the host).

The refinement phase consumes the stream *expanded to posting-level events*
through the inverted index (paper: "probing I_s"), still in descending
order:  (set, q, slot, sim) per posting of each streamed token.

Multi-query serving: :func:`build_token_stream_batch` stacks B queries into
one (sum |Q_b| x |V|) blocked sweep — one provider dispatch and one host
compaction per vocab block for the whole batch — and returns per-query
streams bit-identical to B single-query calls.

A stream depends only on (query, provider, alpha) — NOT on the partition —
so the partition scheduler (``repro.core.scheduler``) builds each query's
stream once and expands it through every partition's inverted index,
replacing the historical per-partition rebuild with P calls to
:func:`expand_to_events` per query.

Cross-REQUEST reuse (DESIGN.md §3.2): because the stream is a pure
function of that (query tokens, alpha, provider) key, repeated or
overlapping requests can skip the blocked sweep entirely —
:class:`TokenStreamCache` is the LRU the request engine (and
``KoiosSearch(stream_cache=...)``) consults, and
:func:`build_token_stream_batch_cached` the cache-aware build that
sweeps only the misses (still as ONE stacked matmul) and returns
streams bit-identical to the uncached batch build.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np

from .inverted_index import InvertedIndex
from .types import SetCollection, pad_ids_pow2, pow2

# The provider sweep (and the cosine_topk kernel) compiles one program
# per stacked-row count; serving coalesces arbitrary request mixes, so
# without the ``pad_ids_pow2`` row bucket every new cohort composition
# would be a fresh compile (pad rows are sliced off — bit-identical).


@dataclasses.dataclass(frozen=True)
class TokenStream:
    """All pairs (q position, token, sim >= alpha), descending by sim."""

    q_pos: np.ndarray    # (T,) int32 — position of the query element in Q
    token: np.ndarray    # (T,) int32 — vocabulary token id
    sim: np.ndarray      # (T,) float32, non-increasing

    def __len__(self) -> int:
        return len(self.sim)


@dataclasses.dataclass(frozen=True)
class EventStream:
    """Posting-level expansion of a TokenStream (still descending by sim)."""

    set_id: np.ndarray   # (E,) int32
    q_pos: np.ndarray    # (E,) int32
    slot: np.ndarray     # (E,) int32 — flat token-array slot (t-side
    #                      identity; int64 only when the repository
    #                      overflows int32 slots — see types.slot_dtype)
    sim: np.ndarray      # (E,) float32, non-increasing
    n_tuples: int        # stream tuples that produced these events

    def __len__(self) -> int:
        return len(self.sim)


def _finalize_stream(query: np.ndarray, q_pos: np.ndarray, token: np.ndarray,
                     sim: np.ndarray, vocab: int) -> TokenStream:
    """Identity-pair completion + global descending sort for one query."""
    nq = len(query)
    # Identity pairs (q, q, 1.0) — add any that the provider missed (e.g.
    # degenerate embeddings) and dedupe.
    in_vocab = query < vocab
    id_q = np.arange(nq, dtype=np.int32)[in_vocab]
    id_t = query[in_vocab]
    key = q_pos.astype(np.int64) * vocab + token
    id_key = id_q.astype(np.int64) * vocab + id_t
    missing = ~np.isin(id_key, key)
    q_pos = np.concatenate([q_pos, id_q[missing]])
    token = np.concatenate([token, id_t[missing]])
    sim = np.concatenate([sim, np.ones(missing.sum(), np.float32)])

    # identity pairs must carry sim exactly 1.0 even if the provider returned
    # a slightly different value
    ident = query[q_pos] == token
    sim = np.where(ident, np.float32(1.0), sim)

    order = np.argsort(-sim, kind="stable")
    return TokenStream(q_pos=q_pos[order], token=token[order], sim=sim[order])


def _build_stream_entries_kernel(stacked: np.ndarray, sim_provider,
                                 alpha: float, block_size: int,
                                 interpret: bool):
    """(row, token, sim >= alpha) triples via the ``cosine_topk`` Pallas
    kernel (DESIGN.md §7) instead of the jnp provider sweep.

    The kernel keeps a running top-k on-chip, so the (rows x |V|) score
    matrix never round-trips to HBM; ``k`` doubles until no row's k-th
    score clears alpha (then the top-k provably contains every >= alpha
    entry).  Per-entry math matches the provider path bit for bit: the
    kernel dots the same L2-normalized rows the provider normalizes per
    block (row-wise normalization is subset-invariant), and clip +
    identity-fix are applied to the returned values exactly as
    ``EmbeddingSimilarity`` applies them to score blocks.  Entries are
    re-ordered to the provider sweep's (vocab block, row, token) order so
    downstream admission order — and therefore every bound — is
    identical.
    """
    import jax.numpy as jnp

    from ..kernels import ops as kops
    from ..runtime import instrument

    vocab = sim_provider.vocab_size
    if not len(stacked):
        z = np.zeros(0, np.int64)
        return z, z.astype(np.int32), np.zeros(0, np.float32)
    # cached device-resident normalized table; query rows gathered on
    # device (no full-table round-trip per call).  Rows pad to a pow2
    # bucket so steady-state serving reuses compiled programs (pad rows
    # are sliced off before any value is consumed — bit-identical).
    from .similarity import normalized_table_for
    table_n = normalized_table_for(sim_provider)
    qe = table_n[jnp.asarray(pad_ids_pow2(stacked))]
    k = min(128, vocab)
    while True:
        instrument.record("h2d:stream_kernel_dispatch")
        instrument.record("d2h:stream_materialize")
        vals, idx = kops.cosine_topk(qe, table_n, k=k, interpret=interpret)
        vals = np.asarray(vals)[:len(stacked)]
        idx = np.asarray(idx)[:len(stacked)]
        if k == vocab or float(vals[:, -1].max()) < alpha:
            break
        k = min(k * 2, vocab)          # a row saturated: deepen the top-k

    # provider-path value semantics: clip to [0, 1], identity pairs 1.0
    vals = np.clip(vals, 0.0, 1.0)
    vals = np.where(idx == stacked[:, None], np.float32(1.0),
                    vals).astype(np.float32)
    rows, cols = np.nonzero(vals >= alpha)
    q_rows = rows.astype(np.int64)
    token = idx[rows, cols].astype(np.int32)
    sim = vals[rows, cols]

    # identity pairs the top-k cutoff may have missed (always >= alpha)
    key = q_rows * vocab + token
    id_key = np.arange(len(stacked), dtype=np.int64) * vocab + stacked
    missing = ~np.isin(id_key, key)
    q_rows = np.concatenate([q_rows, np.nonzero(missing)[0]])
    token = np.concatenate([token, stacked[missing]])
    sim = np.concatenate([sim, np.ones(missing.sum(), np.float32)])

    # the provider sweep emits (block asc, stacked row asc, token asc)
    order = np.lexsort((token, q_rows, token // block_size))
    return q_rows[order], token[order], sim[order]


def build_token_stream_batch(queries, sim_provider, alpha: float,
                             block_size: int = 4096,
                             use_kernel: bool = False,
                             interpret: bool = False) -> "list[TokenStream]":
    """Token streams for B queries from ONE blocked similarity sweep.

    The queries are stacked into a single (sum |Q_b|, |V|-block) similarity
    matmul per vocabulary block — B times fewer provider dispatches and one
    host-side ``>= alpha`` compaction per block instead of B of them.  Rows
    of the stacked result are exactly the rows each per-query call would
    compute, and the per-query finalize (identity pairs, stable sort) is
    shared with :func:`build_token_stream`, so the returned streams are
    bit-identical to the per-query path.

    ``sim_provider`` must expose ``query_vs_vocab_block(q_ids, lo, hi)`` and
    ``vocab_size``.  Identity pairs (q, q) are always included with sim 1.0
    (paper §V: a query element is returned for itself on first probe — this
    initialises bounds with the vanilla overlap and covers out-of-vocabulary
    elements).

    ``use_kernel`` sweeps with the ``cosine_topk`` Pallas kernel instead
    of the provider; ``interpret`` runs that kernel in interpret mode.
    """
    queries = [np.asarray(q, dtype=np.int32) for q in queries]
    if not queries:
        return []
    vocab = sim_provider.vocab_size
    stacked = np.concatenate(queries)
    # row ranges of each query inside the stacked matrix
    bounds = np.zeros(len(queries) + 1, np.int64)
    np.cumsum([len(q) for q in queries], out=bounds[1:])

    # the kernel path computes cosine from the provider's embedding table;
    # any other similarity (e.g. n-gram Jaccard) falls back to the
    # provider sweep — same gate as the fused schedule's
    if use_kernel and getattr(sim_provider, "name", None) == "cosine":
        q_rows, token, sim = _build_stream_entries_kernel(
            stacked, sim_provider, alpha, block_size, interpret)
        out = []
        for b, query in enumerate(queries):
            m = (q_rows >= bounds[b]) & (q_rows < bounds[b + 1])
            out.append(_finalize_stream(
                query, (q_rows[m] - bounds[b]).astype(np.int32),
                token[m], sim[m], vocab))
        return out

    qs = [[] for _ in queries]
    ts = [[] for _ in queries]
    ss = [[] for _ in queries]
    # pow2 row bucket: one compiled sweep program per (bucket, block)
    # instead of one per cohort composition (pad rows sliced off)
    stacked_in = pad_ids_pow2(stacked)
    for lo in range(0, vocab, block_size):
        hi = min(lo + block_size, vocab)
        block = np.asarray(sim_provider.query_vs_vocab_block(
            stacked_in, lo, hi))[:len(stacked)]
        qi, tj = np.nonzero(block >= alpha)          # one compaction, B queries
        if not len(qi):
            continue
        vals = block[qi, tj].astype(np.float32)
        # qi is ascending (row-major nonzero), so each query's rows are one
        # contiguous slice; split at the stacked row bounds
        cuts = np.searchsorted(qi, bounds)
        for b in range(len(queries)):
            s, e = cuts[b], cuts[b + 1]
            if e > s:
                qs[b].append((qi[s:e] - bounds[b]).astype(np.int32))
                ts[b].append((tj[s:e] + lo).astype(np.int32))
                ss[b].append(vals[s:e])

    out = []
    for b, query in enumerate(queries):
        if qs[b]:
            q_pos = np.concatenate(qs[b])
            token = np.concatenate(ts[b])
            sim = np.concatenate(ss[b])
        else:
            q_pos = np.zeros(0, np.int32)
            token = np.zeros(0, np.int32)
            sim = np.zeros(0, np.float32)
        out.append(_finalize_stream(query, q_pos, token, sim, vocab))
    return out


class TokenStreamCache:
    """Byte-bounded LRU cache of token streams keyed by (query tokens,
    alpha, provider, collection epoch).

    Streams are pure functions of the key (module docstring), and
    :class:`TokenStream` is frozen with arrays no consumer mutates, so a
    hit returns the cached object itself — zero copies, bit-identical to
    a rebuild.  The provider component of the key is its ``id`` (the
    provider is pinned by the cache so the id cannot be recycled): two
    providers with equal tables are distinct keys (correct, merely
    conservative), while a provider whose table is mutated in place
    would serve stale streams — providers are immutable by convention
    everywhere else in the repo.

    The bound is BYTES, not entries (``max_bytes``): streams vary ~100x
    in footprint with query size x alpha (a permissive alpha on a large
    query yields a long (q_pos, token, sim) tuple list), so an entry
    count bounds nothing — a byte budget is what actually caps host
    memory.  Entries larger than the whole budget are not cached at all
    (they would only evict everything else and then miss next time).

    The key carries the serving layer's collection EPOCH (DESIGN.md
    §6.5).  Streams do not read the collection — but the entries
    belong to an engine whose refinement/verification state is epoch-
    pinned, and keying by epoch makes "a commit cannot serve stale
    state" a cache invariant rather than a per-caller audit: after
    ``set_epoch`` bumps, every old-epoch entry is unreachable (and
    drains off the LRU cold end under the byte budget).

    ``hits``/``misses``/``evictions`` are cumulative; the request
    engine surfaces them per serving window via
    ``runtime.instrument.EngineCounters``.
    """

    def __init__(self, max_bytes: int = 64 << 20):
        assert max_bytes >= 1
        self.max_bytes = int(max_bytes)
        self.bytes = 0                   # current cached payload bytes
        self.epoch = 0                   # collection epoch key component
        self._entries: "OrderedDict[tuple, TokenStream]" = OrderedDict()
        # pin each keyed provider so its id cannot be recycled by the
        # allocator while entries keyed on it may still be alive (a
        # handful of providers per process; never evicted)
        self._providers: dict = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def set_epoch(self, epoch: int) -> None:
        """Bump the epoch key component (engine resync): entries of
        older epochs become unreachable immediately and age off the LRU
        cold end under the byte budget."""
        self.epoch = int(epoch)

    @staticmethod
    def _nbytes(stream: TokenStream) -> int:
        return (stream.q_pos.nbytes + stream.token.nbytes
                + stream.sim.nbytes)

    def key(self, query: np.ndarray, alpha: float, sim_provider) -> tuple:
        q = np.ascontiguousarray(np.asarray(query, np.int32))
        self._providers[id(sim_provider)] = sim_provider
        return (q.tobytes(), float(alpha), id(sim_provider), self.epoch)

    def __len__(self) -> int:
        return len(self._entries)

    def contains(self, key: tuple) -> bool:
        """Membership probe that touches neither LRU order nor counters
        (per-request hit attribution in the engine)."""
        return key in self._entries

    def get(self, key: tuple):
        """Cached stream for ``key`` (bumping LRU + hit/miss counters)."""
        stream = self._entries.get(key)
        if stream is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return stream

    def put(self, key: tuple, stream: TokenStream) -> None:
        n = self._nbytes(stream)
        if n > self.max_bytes:
            return                        # would evict the whole cache
        prev = self._entries.pop(key, None)
        if prev is not None:
            self.bytes -= self._nbytes(prev)
        self._entries[key] = stream
        self.bytes += n
        while self.bytes > self.max_bytes and self._entries:
            _, old = self._entries.popitem(last=False)
            self.bytes -= self._nbytes(old)
            self.evictions += 1

    def stats(self) -> dict:
        lookups = self.hits + self.misses
        return {"size": len(self._entries), "bytes": self.bytes,
                "max_bytes": self.max_bytes, "epoch": self.epoch,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": self.hits / lookups if lookups else 0.0}

    def describe(self) -> dict:
        """Size-accounting summary (alias of :meth:`stats` — the serving
        observability surface)."""
        return self.stats()


def build_token_stream_batch_cached(queries, sim_provider, alpha: float,
                                    cache: TokenStreamCache,
                                    block_size: int = 4096,
                                    use_kernel: bool = False,
                                    interpret: bool = False
                                    ) -> "list[TokenStream]":
    """Cache-aware :func:`build_token_stream_batch`: hits skip the sweep,
    misses build in ONE stacked sweep and populate the cache.

    Duplicate queries within one call build once (the later occurrences
    count as hits — they are served without a sweep).  Each per-query
    stream is bit-identical to the uncached batch build: rows of the
    stacked sweep are exactly the rows a per-query call computes, so
    sweeping only the misses changes nothing (see the batch builder's
    contract).
    """
    queries = [np.asarray(q, dtype=np.int32) for q in queries]
    keys = [cache.key(q, alpha, sim_provider) for q in queries]
    out: "list[Optional[TokenStream]]" = [None] * len(queries)
    build_idx: "list[int]" = []          # first occurrence of each missed key
    followers: "dict[tuple, list[int]]" = {}
    for i, key in enumerate(keys):
        if key in followers:             # duplicate miss within this call
            followers[key].append(i)
            cache.hits += 1
            continue
        stream = cache.get(key)
        if stream is None:
            build_idx.append(i)
            followers[key] = []
        else:
            out[i] = stream
    if build_idx:
        built = build_token_stream_batch(
            [queries[i] for i in build_idx], sim_provider, alpha,
            block_size=block_size, use_kernel=use_kernel,
            interpret=interpret)
        for i, stream in zip(build_idx, built):
            cache.put(keys[i], stream)
            out[i] = stream
            for j in followers[keys[i]]:
                out[j] = stream
    return out


def build_token_stream(query: np.ndarray, sim_provider, alpha: float,
                       block_size: int = 4096) -> TokenStream:
    """Single-query token stream (see :func:`build_token_stream_batch`)."""
    return build_token_stream_batch([query], sim_provider, alpha,
                                    block_size)[0]


def expand_to_events(stream: TokenStream, index: InvertedIndex) -> EventStream:
    """Expand stream tuples through the inverted index to per-set events.

    Fully vectorized: posting ranges become one flat gather index built from
    repeated range starts plus within-range offsets (cumulative-offset
    trick) — no Python loop over stream tokens.
    """
    counts = index.posting_counts()
    reps = counts[stream.token]
    total = int(reps.sum())
    q_pos = np.repeat(stream.q_pos, reps)
    sim = np.repeat(stream.sim, reps)
    if total:
        starts = index.tok_indptr[stream.token]      # (T,) posting-range lo
        ends = np.cumsum(reps)                       # event offset per tuple
        within = np.arange(total, dtype=np.int64) - np.repeat(ends - reps,
                                                              reps)
        gather = np.repeat(starts, reps) + within
        set_id = index.posting_set[gather]
        slot = index.posting_slot[gather]
    else:
        set_id = np.zeros(0, dtype=np.int32)
        slot = np.zeros(0, dtype=index.posting_slot.dtype)
    return EventStream(set_id=set_id, q_pos=q_pos, slot=slot, sim=sim,
                       n_tuples=len(stream))


def pad_events(events: EventStream, chunk: int):
    """Pad event arrays to a power-of-two number of ``chunk``-sized chunks
    (set_id = -1 padding).  Pow2 chunk counts bound jit recompilations of the
    refinement scan to O(log stream-length) distinct shapes."""
    e = len(events)
    n_chunks = pow2(max(1, -(-e // chunk)))
    total = n_chunks * chunk
    pad = total - e

    def _pad(x, fill):
        return np.concatenate([x, np.full(pad, fill, dtype=x.dtype)])

    # pad sims repeat the final (lowest) real sim — a valid stream
    # position for the filter pass.  An EMPTY stream has no valid
    # position: pad with 0.0 (not 1.0 — a 1.0 s_now would inflate the
    # pad chunk's unseen-remainder term if any set were ever marked
    # seen; with 0.0 the pad chunk is inert by construction).
    last_sim = events.sim[-1] if e else np.float32(0.0)
    return (
        _pad(events.set_id, -1).reshape(n_chunks, chunk),
        _pad(events.q_pos, 0).reshape(n_chunks, chunk),
        _pad(events.slot, 0).reshape(n_chunks, chunk),
        _pad(events.sim, last_sim).reshape(n_chunks, chunk),
    )


def event_ranks(ev_set: np.ndarray) -> np.ndarray:
    """Within-(chunk, set) occurrence index of every event — the
    *set-segmented* layout metadata of the refinement scan (DESIGN.md
    §2): events with rank t form level t of the segmented admission
    schedule, and within a level all events touch distinct sets.

    ``ev_set`` is the (n_chunks, chunk) padded set-id array from
    :func:`pad_events`; returns an int32 array of the same shape.
    Padding events (set -1) receive ranks too (they group as one
    segment) but are masked out of both the admission and the
    level-count computation by their sentinel set id.
    """
    n, c = ev_set.shape
    m = n * c
    if m == 0:
        return np.zeros((n, c), np.int32)
    flat_set = ev_set.reshape(-1).astype(np.int64)
    iota = np.arange(m, dtype=np.int64)
    chunk_of = iota // c
    order = np.lexsort((iota, flat_set, chunk_of))   # stable within segment
    key_chunk = chunk_of[order]
    key_set = flat_set[order]
    start = np.ones(m, bool)
    start[1:] = (key_chunk[1:] != key_chunk[:-1]) \
        | (key_set[1:] != key_set[:-1])
    seg_start = np.maximum.accumulate(np.where(start, iota, 0))
    rank = np.empty(m, np.int32)
    rank[order] = (iota - seg_start).astype(np.int32)
    return rank.reshape(n, c)


def pack_events_segmented(ev_set: np.ndarray, ev_q: np.ndarray,
                          ev_slot: np.ndarray, ev_sim: np.ndarray):
    """Lane-pack padded event chunks into the set-segmented (W, L)
    layout the segmented refinement scan consumes (DESIGN.md §2).

    Row ``t`` of a chunk holds its level-``t`` events — the rank-``t``
    event of every set that has one — compacted left into ``L`` fixed-
    width pow2 lanes (set id -1 pads).  Within a row all events touch
    pairwise-distinct sets, so the scan admits a whole row as one
    vectorized scatter; down the rows each set's events appear in
    stream order, preserving the only load-bearing order.  ``W`` (pow2)
    covers the deepest per-set segment and ``L`` (pow2) the widest
    level across all chunks, so the packed arrays are at most a small
    constant larger than the flat chunks while the sequential depth
    drops from ``chunk`` to ``W``.

    Returns (set (n, W, L), q, slot, sim, s_now (n,)) — ``s_now`` is
    each chunk's final stream-order sim (the filter-pass position that
    the packed layout no longer encodes positionally).
    """
    n, c = ev_set.shape
    ranks = event_ranks(ev_set)
    flat_valid = (ev_set >= 0).reshape(-1)
    flat_rank = ranks.reshape(-1).astype(np.int64)
    m = n * c
    iota = np.arange(m, dtype=np.int64)
    chunk_of = iota // c
    vidx = iota[flat_valid]
    order = np.lexsort((vidx, flat_rank[flat_valid], chunk_of[flat_valid]))
    vs = vidx[order]
    nv = len(vs)
    key_c, key_r = chunk_of[vs], flat_rank[vs]
    start = np.ones(nv, bool)
    if nv:
        start[1:] = (key_c[1:] != key_c[:-1]) | (key_r[1:] != key_r[:-1])
    lane = np.arange(nv) - np.maximum.accumulate(
        np.where(start, np.arange(nv), 0)) if nv else np.zeros(0, np.int64)
    W = pow2(int(key_r.max()) + 1 if nv else 1)
    L = pow2(int(lane.max()) + 1 if nv else 1)

    set3 = np.full((n, W, L), -1, np.int32)
    q3 = np.zeros((n, W, L), np.int32)
    slot3 = np.zeros((n, W, L), ev_slot.dtype)
    sim3 = np.zeros((n, W, L), np.float32)
    set3[key_c, key_r, lane] = ev_set.reshape(-1)[vs]
    q3[key_c, key_r, lane] = ev_q.reshape(-1)[vs]
    slot3[key_c, key_r, lane] = ev_slot.reshape(-1)[vs]
    sim3[key_c, key_r, lane] = ev_sim.reshape(-1)[vs]
    return set3, q3, slot3, sim3, ev_sim[:, -1].astype(np.float32)
