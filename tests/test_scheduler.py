"""Partition scheduler: the one-shot search (one wave per partition,
theta_lb carried between partitions) matches the brute-force oracle, fused
device waves are bit-identical to host waves (across partitions x batch x
verifier modes), theta_lb is monotone over the partition waves, and the
mesh bound exchange changes nothing."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EmbeddingSimilarity, ExecutionPlan, KoiosIndex,
                        KoiosSearch, SearchParams, brute_force_topk,
                        partition_ranges, run_plan)
from repro.data import make_collection, make_embeddings, sample_queries


def _params(**kw):
    return SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8, **kw)


@pytest.fixture(scope="module")
def brute_force(small_world):
    """The oracle's top-k of each query batch of the grid below, computed
    once per batch (it depends on neither verifier nor partitions)."""
    coll, sim = small_world
    index = KoiosIndex.build(coll)
    return {batch: [brute_force_topk(index, q, sim, _params())
                    for q in sample_queries(coll, batch, seed=5)]
            for batch in (1, 8)}


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("partitions", [1, 2, 4])
@pytest.mark.parametrize("batch", [1, 8])
def test_one_shot_matches_brute_force(small_world, brute_force, verifier,
                                      partitions, batch):
    """The one-shot search on host waves (one wave per partition, the
    running theta_lb carried from partition to partition) returns the
    oracle's top-k scores, whatever the verifier and the split."""
    coll, sim = small_world
    engine = KoiosSearch(coll, sim, _params(verifier=verifier, fused="off"),
                         partitions=partitions)
    queries = sample_queries(coll, batch, seed=5)
    results = engine.search_batch(queries)
    assert engine.scheduler_stats.schedule == "wave"
    assert engine.scheduler_stats.waves == partitions
    for res, ref in zip(results, brute_force[batch]):
        assert 0 < len(res.lb) <= 5
        assert np.allclose(np.sort(res.lb), np.sort(ref.lb[:len(res.lb)]),
                           atol=1e-3)


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("partitions", [1, 2, 4])
@pytest.mark.parametrize("batch", [1, 8])
def test_fused_matches_host_waves_bitwise(small_world, verifier, partitions,
                                          batch):
    """The fused on-device waves (refinement chunk scans + compaction +
    the first R verification rounds as ONE device program per partition
    wave, interpret mode on CPU) return the same ids and the same lb/ub
    floats as host waves."""
    coll, sim = small_world
    queries = sample_queries(coll, batch, seed=5)
    host = KoiosSearch(coll, sim, _params(verifier=verifier, fused="off"),
                       partitions=partitions).search_batch(queries)
    engine = KoiosSearch(coll, sim,
                         _params(verifier=verifier, fused="interpret"),
                         partitions=partitions)
    fus = engine.search_batch(queries)
    st = engine.scheduler_stats
    assert st.schedule == "fused"          # really took the wave path
    assert st.waves == partitions
    for a, c in zip(host, fus):
        assert np.array_equal(a.ids, c.ids)
        assert np.array_equal(a.lb, c.lb)          # bit-identical floats
        assert np.array_equal(a.ub, c.ub)


def test_fused_falls_back_to_host_waves_off_tpu(small_world, monkeypatch):
    """``fused='auto'`` runs host waves off-TPU, and says so (exact
    results either way).  On a TPU backend nothing falls back quietly: a
    fused request the wave cannot serve raises, and only ``fused='off'``
    resolves it to host waves."""
    from repro.core import NGramJaccardSimilarity

    coll, sim = small_world
    params = _params()                                      # fused='auto'
    engine = KoiosSearch(coll, sim, params, partitions=2)
    q = sample_queries(coll, 1, seed=9)[0]
    r_auto = engine.search(q)
    assert engine.scheduler_stats.schedule == "wave"
    assert engine.scheduler_stats.device_rounds == 0
    r_off = KoiosSearch(coll, sim, dataclasses.replace(params, fused="off"),
                        partitions=2).search(q)
    assert np.array_equal(r_auto.ids, r_off.ids)
    assert np.array_equal(r_auto.lb, r_off.lb)

    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ngram = NGramJaccardSimilarity(
        (np.random.default_rng(0).random((coll.vocab_size, 64)) > 0.7)
        .astype(np.float32))
    with pytest.raises(ValueError, match="fused wave"):
        KoiosSearch(coll, ngram, params, partitions=2).search(q)
    off = KoiosSearch(coll, ngram, dataclasses.replace(params, fused="off"),
                      partitions=2)
    off.search(q)
    assert off.scheduler_stats.schedule == "wave"


def test_fused_with_mesh_exchange_identical(small_world):
    """Fused waves with the mesh all-reduce-max bound exchange between
    partitions (single-device mesh: identity) change no result."""
    from repro.launch.mesh import bound_exchange_mesh
    from repro.runtime.sharding import bound_exchange_for

    coll, sim = small_world
    mesh = bound_exchange_mesh()
    params = _params(fused="interpret")
    host = KoiosSearch(coll, sim, params, partitions=4)
    meshed = KoiosSearch(coll, sim, params, partitions=4,
                         bound_exchange=bound_exchange_for(mesh))
    queries = sample_queries(coll, 3, seed=41)
    for a, b in zip(host.search_batch(queries),
                    meshed.search_batch(queries)):
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
    """A query's trajectory through the scheduler must not depend on
    which other queries share the plan (per-query bounds, shared
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
    plan's partition waves (one trace row each), and the final bound is
    what the tiles report."""
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
    assert len(trace) == partitions
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
    assert st.waves == 4
    assert len(st.theta_trace) == 4          # one row per partition wave
    d = st.as_dict()
    assert isinstance(d["theta_trace"], list)
    assert len(d["theta_trace"]) == 4
