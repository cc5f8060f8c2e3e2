"""Jit-recompilation guards: DESIGN.md §2 promises that pow2 padding
everywhere (chunk counts, bitmask words, solver batches, wave shapes)
bounds the number of compiled program variants to O(log shape).  These
tests sweep input sizes across orders of magnitude and count the actual
jit cache growth."""
import math

import numpy as np
import pytest

from repro.core import EmbeddingSimilarity, KoiosSearch, SearchParams
from repro.core.refinement import _run_refinement, run_refinement
from repro.core.token_stream import EventStream
from repro.data import make_collection, make_embeddings, sample_queries


def _synthetic_events(rng, n_events: int, num_sets: int, nq: int,
                      total_slots: int) -> EventStream:
    sim = np.sort(rng.random(n_events).astype(np.float32))[::-1]
    return EventStream(
        set_id=rng.integers(0, num_sets, n_events).astype(np.int32),
        q_pos=rng.integers(0, nq, n_events).astype(np.int32),
        slot=rng.integers(0, total_slots, n_events).astype(np.int64),
        sim=sim, n_tuples=n_events)


def test_refinement_variants_log_in_stream_length():
    """Stream lengths across 3 orders of magnitude compile O(log) scan
    variants: pow2 chunk counts, plus the segmented layout's pow2
    (W, L) lane grid — both lane dims are bounded by the (fixed) chunk
    size, so the growth in STREAM LENGTH stays the chunk-count log and
    the grid contributes a small additive factor.  A second sweep of
    the same lengths must compile nothing (the bucketing is the point)."""
    rng = np.random.default_rng(0)
    num_sets, nq, total_slots, chunk = 50, 8, 400, 64
    sizes = rng.integers(2, 12, num_sets).astype(np.int64)
    sizes = np.minimum(sizes, total_slots // num_sets)
    before = _run_refinement._cache_size()
    lengths = [1, 3, 7, 20, 55, 130, 300, 701, 1500, 2500]

    def sweep():
        sweep_rng = np.random.default_rng(1)
        for L in lengths:
            ev = _synthetic_events(sweep_rng, L, num_sets, nq, total_slots)
            run_refinement(ev, sizes.astype(np.int32), nq, total_slots,
                           k=5, alpha=0.8, chunk_size=chunk)

    sweep()
    variants = _run_refinement._cache_size() - before
    max_chunks = -(-max(lengths) // chunk)
    # pow2 chunk counts + the pow2 lane grid at this (fixed) chunk size
    bound = math.ceil(math.log2(max_chunks)) + 2 \
        + math.ceil(math.log2(chunk))
    assert variants <= bound, (variants, bound)
    mid = _run_refinement._cache_size()
    sweep()                              # identical shapes: no growth
    assert _run_refinement._cache_size() == mid


def test_engine_sweep_compiles_olog(small_world):
    """End-to-end: a sweep of query cardinalities (and thus stream/solver
    shapes) through the engine stays within an O(log) compile budget for
    the refinement scan and both solver entry points."""
    from repro.core.matching.auction import auction_batch
    from repro.core.matching.hungarian import hungarian_batch

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          verifier="hybrid")
    engine = KoiosSearch(coll, sim, params, partitions=2)
    rng = np.random.default_rng(2)
    sweep = [1, 2, 3, 5, 8, 11, 16, 23, 32]
    queries = [np.asarray(rng.choice(coll.vocab_size, size=nq,
                                     replace=False), np.int32)
               for nq in sweep]
    before = (_run_refinement._cache_size(),
              auction_batch._cache_size(), hungarian_batch._cache_size())
    for q in queries:
        engine.search(q, schedule="overlap")
    grew = (_run_refinement._cache_size() - before[0],
            auction_batch._cache_size() - before[1],
            hungarian_batch._cache_size() - before[2])
    # 9 distinct |Q| values with streams spanning ~2 orders of magnitude.
    # Every padded dim is pow2, so variant counts are bounded by products
    # of log factors (nq_pad in {8,16,32} x c_pad in {8,16,32} at this
    # scale, plus the segmented layout's pow2 lane grid at the fixed
    # chunk size), never by the number of distinct logical shapes seen.
    assert grew[0] <= math.ceil(math.log2(1 + 2500 // 64)) + 2 \
        + 2 * math.ceil(math.log2(64)), grew
    assert grew[1] <= 3 * 3 + 1, grew          # (nq_pad x c_pad) grid
    assert grew[2] <= 3 * 3 + 1, grew
    # the actual recompile guard: a second identical sweep compiles NOTHING
    mid = (_run_refinement._cache_size(),
           auction_batch._cache_size(), hungarian_batch._cache_size())
    for q in queries:
        engine.search(q, schedule="overlap")
    assert (_run_refinement._cache_size(),
            auction_batch._cache_size(),
            hungarian_batch._cache_size()) == mid


def _jit_cache_sizes():
    from repro.core.matching.auction import auction_batch
    from repro.core.matching.hungarian import hungarian_batch
    from repro.core.similarity import _cosine_block, device_weights

    return (_run_refinement._cache_size(), auction_batch._cache_size(),
            hungarian_batch._cache_size(), _cosine_block._cache_size(),
            device_weights._cache_size())


def test_engine_steady_state_zero_recompiles(small_world):
    """The request-engine tentpole invariant (DESIGN.md §3.2): after
    warmup, a steady-state serving sweep of VARYING batch sizes within
    one pow2 bucket — different cohort compositions, different verify
    round shapes, stream-cache hits and misses — compiles NOTHING:
    refinement scans, both solvers, and the provider similarity blocks
    all reuse pow2-bucketed programs."""
    from repro.runtime.engine import RequestEngine

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          verifier="hybrid")
    pool = sample_queries(coll, 8, seed=3)
    sweep = [5, 6, 7, 8, 6, 5]           # one pow2 bucket (pads to 8)
    rng = np.random.default_rng(4)
    batches = [[pool[i] for i in rng.choice(8, size=bs, replace=False)]
               for bs in sweep]

    def serve_all():
        eng = RequestEngine(coll, sim, params, partitions=2)
        eng.warmup(pool)
        for batch in batches:
            eng.serve(batch)

    serve_all()                          # prime every bucketed shape
    before = _jit_cache_sizes()
    serve_all()                          # steady state: zero recompiles
    assert _jit_cache_sizes() == before


def test_lane_cover_is_pow2_up_to_256_then_lane_multiples():
    """The cover of a set-size axis keeps every pow2 shape up to 256 (so
    short-set deployments compile what they did) and above it grows by
    whole 128-lane tiles."""
    from repro.core.types import lane_cover, pow2

    for n in range(1, 257):
        assert lane_cover(n) == pow2(n)
        assert lane_cover(n, 8) == pow2(n, 8)
    assert [lane_cover(n) for n in (257, 384, 385, 514, 640, 1024)] == \
        [384, 384, 512, 640, 640, 1024]
    assert all(lane_cover(n) % 128 == 0 for n in range(129, 4097))
    assert all(n <= lane_cover(n) < n + 128 for n in range(129, 4097))


def test_engine_steady_state_zero_recompiles_at_the_lane_cover(dblp_world):
    """The steady-state invariant on a corpus whose largest set (260) the
    cover pads to 384, not 512: the pool and every shard's dense operands
    take the cover of their own largest set, and after one pass a second
    pass of varying batch sizes compiles nothing."""
    from repro.core.types import lane_cover
    from repro.runtime.engine import RequestEngine

    coll, sim = dblp_world
    params = SearchParams(k=5, alpha=0.8, verify_batch=8)
    pool = sample_queries(coll, 6, seed=3)
    batches = [pool[:bs] for bs in (3, 4, 2)]

    def serve_all():
        eng = RequestEngine(coll, sim, params, partitions=2)
        for batch in batches:
            eng.serve(batch)
        return eng

    eng = serve_all()
    assert eng.pool._c_pad == 384
    for shard in eng.partitions:
        assert shard.wave_operands()[2] == lane_cover(
            int(shard.coll.set_sizes.max()))
    before = _jit_cache_sizes()
    serve_all()
    assert _jit_cache_sizes() == before


def test_fused_engine_steady_state_zero_recompiles(small_world):
    """Same invariant through the fused device-wave engine: wave configs
    depend only on pow2-padded shapes, so a steady-state sweep of batch
    sizes within one pow2 bucket reuses the compiled wave programs."""
    from repro.core.wave import _wave_fn
    from repro.runtime.engine import RequestEngine

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret")
    pool = sample_queries(coll, 8, seed=3)
    batches = [pool[:bs] for bs in (5, 6, 7, 8, 6)]

    def serve_all():
        eng = RequestEngine(coll, sim, params, partitions=2,
                            schedule="fused")
        assert eng.schedule == "fused"
        for batch in batches:
            eng.serve(batch)

    serve_all()                          # prime the wave-config grid
    before = (_wave_fn.cache_info().currsize, _jit_cache_sizes())
    serve_all()                          # steady state: zero recompiles
    assert (_wave_fn.cache_info().currsize, _jit_cache_sizes()) == before


def test_sharded_engine_warmup_zero_steady_state_recompiles(small_world):
    """PR-6 invariant: engine warmup sweeps the SHARD-LOCAL pow2
    chunk-bucket grid (each shard's inverted index yields different
    event counts for the same query), so a 4-shard fused engine serving
    varying batch sizes within one pow2 bucket compiles NOTHING after
    warmup — wave programs, refinement scans, solvers, similarity
    blocks, and the top-k merge tree are all primed per shard."""
    from repro.core.search import _merge_tree_fn
    from repro.core.wave import _wave_fn
    from repro.runtime.collection import ShardedCollection
    from repro.runtime.engine import RequestEngine

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret")
    sc = ShardedCollection.build(coll, 4)
    pool = sample_queries(coll, 8, seed=3)
    batches = [pool[:bs] for bs in (5, 6, 7, 8, 6)]

    eng = RequestEngine(None, sim, params, schedule="fused", collection=sc)
    assert eng.schedule == "fused"
    assert eng.collection is sc
    eng.warmup(pool)
    before = (_wave_fn.cache_info().currsize,
              _merge_tree_fn.cache_info().currsize, _jit_cache_sizes())
    for batch in batches:
        eng.serve(batch)
    assert (_wave_fn.cache_info().currsize,
            _merge_tree_fn.cache_info().currsize,
            _jit_cache_sizes()) == before


def test_fused_wave_variants_shared_across_batches(small_world):
    """The wave program's static config depends only on pow2-padded
    shapes: rerunning the fused schedule with a different batch of the
    same padded size must not recompile."""
    from repro.core.wave import _wave_fn

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret")
    engine = KoiosSearch(coll, sim, params, partitions=2)
    q1 = sample_queries(coll, 3, seed=1)
    q2 = sample_queries(coll, 3, seed=2)
    engine.search_batch(q1, schedule="fused")
    n_fns = _wave_fn.cache_info().currsize
    engine.search_batch(q1, schedule="fused")       # same shapes: no growth
    assert _wave_fn.cache_info().currsize == n_fns
    engine.search_batch(q2, schedule="fused")       # new batch: pow2 reuse
    assert _wave_fn.cache_info().currsize <= n_fns + 2
