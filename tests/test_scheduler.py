"""Partition scheduler: overlapped execution is bit-identical to the
sequential partition loop, the fused on-device wave schedule is
bit-identical to both (across partitions x batch x verifier modes), theta_lb
is monotone over scheduler steps, and the mesh bound exchange changes
nothing."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EmbeddingSimilarity, ExecutionPlan, KoiosSearch,
                        SearchParams, partition_ranges, run_plan)
from repro.data import make_collection, make_embeddings, sample_queries


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("partitions", [1, 2, 4])
@pytest.mark.parametrize("batch", [1, 8])
def test_overlap_matches_sequential_bitwise(small_world, verifier,
                                            partitions, batch):
    """The tentpole guarantee: the overlapped partition schedule (async
    refinement dispatch, global verify queue, bidirectional bounds)
    returns the same ids and the same lb/ub floats as the pre-scheduler
    sequential running-max loop."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          verifier=verifier)
    engine = KoiosSearch(coll, sim, params, partitions=partitions)
    queries = sample_queries(coll, batch, seed=5)
    seq = engine.search_batch(queries, schedule="sequential")
    ovl = engine.search_batch(queries, schedule="overlap")
    for a, b in zip(seq, ovl):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)          # bit-identical floats
        assert np.array_equal(a.ub, b.ub)


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("partitions", [1, 2, 4])
@pytest.mark.parametrize("batch", [1, 8])
def test_fused_matches_overlap_and_sequential_bitwise(small_world, verifier,
                                                      partitions, batch):
    """The PR-3 tentpole guarantee: the fused on-device wave schedule
    (refinement chunk scans + compaction + the first R verification
    rounds as ONE device program per partition wave, interpret mode on
    CPU) returns the same ids and the same lb/ub floats as both host
    schedules."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          verifier=verifier, fused="interpret")
    engine = KoiosSearch(coll, sim, params, partitions=partitions)
    queries = sample_queries(coll, batch, seed=5)
    seq = engine.search_batch(queries, schedule="sequential")
    ovl = engine.search_batch(queries, schedule="overlap")
    fus = engine.search_batch(queries, schedule="fused")
    st = engine.scheduler_stats
    assert st.schedule == "fused"          # really took the wave path
    assert st.waves == partitions
    for a, b, c in zip(seq, ovl, fus):
        assert np.array_equal(a.ids, c.ids)
        assert np.array_equal(a.lb, c.lb)          # bit-identical floats
        assert np.array_equal(a.ub, c.ub)
        assert np.array_equal(b.ids, c.ids)
        assert np.array_equal(b.lb, c.lb)
        assert np.array_equal(b.ub, c.ub)


def test_fused_falls_back_to_overlap_off_tpu(small_world, monkeypatch):
    """``fused='auto'`` resolves a fused request to overlap off-TPU, and
    says so (exact results either way).  On a TPU backend nothing falls
    back quietly: a fused request the wave cannot serve raises, and only
    ``fused='off'`` resolves it to overlap."""
    from repro.core import NGramJaccardSimilarity

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)
    engine = KoiosSearch(coll, sim, params, partitions=2)   # schedule=fused
    q = sample_queries(coll, 1, seed=9)[0]
    r_fused = engine.search(q)
    assert engine.scheduler_stats.schedule == "overlap"
    assert engine.scheduler_stats.waves == 0
    r_seq = engine.search(q, schedule="sequential")
    assert np.array_equal(r_fused.ids, r_seq.ids)
    assert np.array_equal(r_fused.lb, r_seq.lb)

    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ngram = NGramJaccardSimilarity(
        (np.random.default_rng(0).random((coll.vocab_size, 64)) > 0.7)
        .astype(np.float32))
    with pytest.raises(ValueError, match="fused schedule"):
        KoiosSearch(coll, ngram, params, partitions=2).search(q)
    off = KoiosSearch(coll, ngram, dataclasses.replace(params, fused="off"),
                      partitions=2)
    off.search(q)
    assert off.scheduler_stats.schedule == "overlap"


def test_fused_with_mesh_exchange_identical(small_world):
    """The fused schedule with the mesh all-reduce-max bound exchange at
    its exchange points (single-device mesh: identity) changes no
    result."""
    from repro.launch.mesh import bound_exchange_mesh
    from repro.runtime.sharding import bound_exchange_for

    coll, sim = small_world
    mesh = bound_exchange_mesh()
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret")
    host = KoiosSearch(coll, sim, params, partitions=4)
    meshed = KoiosSearch(coll, sim, params, partitions=4,
                         bound_exchange=bound_exchange_for(mesh))
    queries = sample_queries(coll, 3, seed=41)
    for a, b in zip(host.search_batch(queries, schedule="fused"),
                    meshed.search_batch(queries, schedule="fused")):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)


@pytest.mark.parametrize("partitions", [2, 4])
def test_token_balanced_partitioning(small_world, partitions):
    """Size-balanced (token-count) partitioning (DESIGN.md §9 item 5,
    resolved): identical top-k to the linspace set-range split, and every
    partition's token count within 10% of the ideal share."""
    coll, sim = small_world
    sizes = coll.set_sizes
    bounds = partition_ranges(sizes, partitions, by="tokens")
    assert bounds[0] == 0 and bounds[-1] == coll.num_sets
    assert np.all(np.diff(bounds) > 0)             # non-empty partitions
    tokens = np.array([sizes[lo:hi].sum()
                       for lo, hi in zip(bounds[:-1], bounds[1:])])
    ideal = coll.total_tokens / partitions
    assert tokens.max() <= 1.1 * ideal, (tokens, ideal)

    # token-skewed repository: one huge set drags every greedy cut right;
    # the forward+backward passes must still yield non-empty partitions
    skewed = partition_ranges(np.array([1, 1, 1, 100]), 4, by="tokens")
    assert np.array_equal(skewed, [0, 1, 2, 3, 4])

    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)
    by_sets = KoiosSearch(coll, sim, params, partitions=partitions)
    by_tokens = KoiosSearch(coll, sim, params, partitions=partitions,
                            partition_by="tokens")
    queries = sample_queries(coll, 4, seed=13)
    for a, b in zip(by_sets.search_batch(queries),
                    by_tokens.search_batch(queries)):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)


def test_search_is_search_batch_is_the_scheduler(small_world):
    """Entry-point collapse: ``search`` == ``search_batch`` with B=1 ==
    a 1-partition plan through ``run_plan`` (plus the top-k merge)."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)
    engine = KoiosSearch(coll, sim, params)
    q = sample_queries(coll, 1, seed=23)[0]
    r_single = engine.search(q)
    (r_batch,) = engine.search_batch([q])
    assert np.array_equal(r_single.ids, r_batch.ids)
    assert np.array_equal(r_single.lb, r_batch.lb)
    assert r_single.stats.as_dict() == r_batch.stats.as_dict()
    plan = ExecutionPlan(engine.partitions, [q], pool_coll=coll)
    [tiles] = run_plan(plan, sim, params)
    from repro.core import merge_topk
    r_plan = merge_topk(tiles, params.k)
    assert np.array_equal(r_single.ids, r_plan.ids)
    assert np.array_equal(r_single.lb, r_plan.lb)


def test_batch_rows_independent_of_batch_composition(small_world):
    """A query's trajectory through the overlapped scheduler must not
    depend on which other queries share the plan (per-query bounds, shared
    execution only)."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)
    engine = KoiosSearch(coll, sim, params, partitions=3)
    queries = sample_queries(coll, 4, seed=31)
    batch = engine.search_batch(queries)
    for q, rb in zip(queries, batch):
        rs = engine.search(q)
        assert np.array_equal(rs.ids, rb.ids)
        assert np.array_equal(rs.lb, rb.lb)
        assert rs.stats.as_dict() == rb.stats.as_dict()


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 4))
def test_theta_monotone_over_scheduler_steps(seed, partitions):
    """Property: every query's theta_lb is non-decreasing across the
    scheduler's exchange points (initial refinement exchange + one per
    verification round), and the final bound is what the tiles report."""
    rng = np.random.default_rng(seed)
    coll = make_collection(num_sets=60, vocab_size=300, avg_size=6,
                           max_size=12, seed=seed)
    emb = make_embeddings(300, dim=16, cluster_size=3.0, seed=seed)
    sim = EmbeddingSimilarity(emb)
    params = SearchParams(k=3, alpha=0.8, chunk_size=64, verify_batch=4)
    engine = KoiosSearch(coll, sim, params, partitions=partitions)
    queries = sample_queries(coll, 3, seed=seed)
    results = engine.search_batch(queries)
    trace = engine.scheduler_stats.theta_trace
    assert len(trace) >= 1
    for prev, cur in zip(trace, trace[1:]):
        assert np.all(cur >= prev - 1e-12), (prev, cur)
    for qi, res in enumerate(results):
        # the traced bound is a certified lower bound on the k-th score
        if len(res.lb) >= params.k:
            assert trace[-1][qi] <= res.lb[params.k - 1] + 1e-6


def test_mesh_bound_exchange_identical(small_world):
    """Plugging the mesh all-reduce-max into the exchange changes no
    result (single-device mesh: the reduction is the identity)."""
    from repro.launch.mesh import bound_exchange_mesh
    from repro.runtime.sharding import all_reduce_max, bound_exchange_for

    mesh = bound_exchange_mesh()
    v = np.array([0.25, 1.5, 0.0], np.float32)
    np.testing.assert_array_equal(all_reduce_max(v, mesh), v)

    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)
    host = KoiosSearch(coll, sim, params, partitions=4)
    meshed = KoiosSearch(coll, sim, params, partitions=4,
                         bound_exchange=bound_exchange_for(mesh))
    queries = sample_queries(coll, 3, seed=41)
    for a, b in zip(host.search_batch(queries), meshed.search_batch(queries)):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)


def test_scheduler_stats_populated(small_world):
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8)
    engine = KoiosSearch(coll, sim, params, partitions=4)
    queries = sample_queries(coll, 2, seed=7)
    engine.search_batch(queries)
    st = engine.scheduler_stats
    assert st.tiles == 4 * len(queries)
    assert st.rounds >= 1
    assert st.fused_requests >= st.rounds
    assert st.backward_raises <= st.bound_raises
    d = st.as_dict()
    assert isinstance(d["theta_trace"], list)
