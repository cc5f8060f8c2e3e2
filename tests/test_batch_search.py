"""Batched multi-query pipeline: bit-exact equivalence with per-query
search across verifier modes, batched token-stream equivalence, and the
vectorized event expansion."""
import dataclasses

import numpy as np
import pytest

from repro.core import (EmbeddingSimilarity, InvertedIndex, KoiosSearch,
                        SearchParams, build_token_stream,
                        build_token_stream_batch, expand_to_events)
from repro.data import make_collection, make_embeddings, sample_queries
from repro.runtime.engine import RequestEngine
from test_verify_weights import _reference_so


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("partitions", [1, 3])
def test_search_batch_bit_identical(small_world, verifier, partitions):
    """search_batch(queries) == [search(q) for q in queries], bitwise:
    same ids, same lb/ub floats, same per-phase statistics."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          verifier=verifier)
    engine = KoiosSearch(coll, sim, params, partitions=partitions)
    queries = sample_queries(coll, 5, seed=5)
    batch = engine.search_batch(queries)
    assert len(batch) == len(queries)
    for q, rb in zip(queries, batch):
        rs = engine.search(q)
        assert np.array_equal(rs.ids, rb.ids)
        assert np.array_equal(rs.lb, rb.lb)          # bit-identical floats
        assert np.array_equal(rs.ub, rb.ub)
        assert rs.stats.as_dict() == rb.stats.as_dict()


def _long_query(coll, n):
    """A query of ``n`` distinct tokens, longer than any set of the
    corpus: the tokens of its largest sets, largest first."""
    order = np.argsort(-coll.set_sizes, kind="stable")
    toks = np.concatenate([coll.get_set(int(i)) for i in order])
    _, first = np.unique(toks, return_index=True)
    q = toks[np.sort(first)][:n].astype(np.int32)
    assert len(q) == n > coll.set_sizes.max()
    return q


def _record_launches(monkeypatch):
    """The ``WaveConfig`` of every wave launched from here on."""
    from repro.core import wave

    launched, launch = [], wave.WaveRunner.launch_wave

    def spy(self, *a, **kw):
        out = launch(self, *a, **kw)
        launched.append(out[0].cfg)
        return out

    monkeypatch.setattr(wave.WaveRunner, "launch_wave", spy)
    return launched


def _assert_exact(coll, sim, params, q, result):
    """Top-k ids and scores of ``result`` against the float64 NumPy/SciPy
    reference over every set of the corpus."""
    ref = np.array([_reference_so(coll, sim, q, i, params.alpha)
                    for i in range(coll.num_sets)])
    # sets sharing no similar token are never candidates
    top = np.sort(ref[ref > 0])[::-1][:params.k]
    ids, lb = result.ids, result.lb.astype(np.float64)
    assert len(ids) == len(top) > 0
    np.testing.assert_allclose(lb, ref[ids], rtol=0, atol=1e-4)
    np.testing.assert_allclose(lb, top, rtol=0, atol=1e-4)


@pytest.mark.parametrize("longest", [None, 300, 514])
def test_fused_engine_on_a_dblp_shaped_corpus_matches_the_reference(
        dblp_world, monkeypatch, longest):
    """Large sets through the fused engine (interpret mode): the
    candidates pad to the 384 cover, and the wave's query rows to the
    cover of the cohort's longest query (384 for 300 tokens, 640 for 514,
    where a power of two gave 512 and 1024).  The served batch agrees with
    a float64 NumPy/SciPy reference in top-k ids and scores, and each
    query searched alone, fused or on the host path, gives the batch's
    answer bit for bit."""
    from repro.core.types import lane_cover

    coll, sim = dblp_world
    params = SearchParams(k=5, alpha=0.8, verify_batch=8, fused="interpret")
    sizes = coll.set_sizes
    order = np.argsort(sizes, kind="stable")
    picks = [order[-1], order[np.searchsorted(sizes[order], 130)],
             order[len(order) // 2]]
    queries = [coll.get_set(int(i)) for i in picks]
    if longest:
        queries.insert(1, _long_query(coll, longest))
    eng = RequestEngine(coll, sim, params, schedule="fused")
    assert eng.schedule == "fused" and eng.pool._c_pad == 384
    assert eng.partitions[0].wave_operands()[2] == 384
    launched = _record_launches(monkeypatch)
    served = eng.serve(queries)
    nq_max = max(len(q) for q in queries)
    assert {c.nq_pad for c in launched} == {lane_cover(nq_max)}
    searches = {fused: KoiosSearch(coll, sim,
                                   dataclasses.replace(params, fused=fused))
                for fused in ("interpret", "off")}
    for q, r in zip(queries, served):
        assert r.status == "ok"
        _assert_exact(coll, sim, params, q, r.result)
        for fused, search in searches.items():
            alone = search.search(q)
            assert np.array_equal(alone.ids, r.result.ids), fused
            assert np.array_equal(alone.lb, r.result.lb), fused
            assert np.array_equal(alone.ub, r.result.ub), fused


def test_a_query_longer_than_every_set_of_its_shard_stays_exact(
        dblp_world, monkeypatch):
    """The wave's solver widens its columns to the query rows where the
    query pad exceeds the shard's candidate cover (a 300-token query pads
    to 384 rows; each of three shards covers its own largest set, one of
    them with 256 columns): the answer still equals the float64
    reference, and the host path's."""
    from repro.core.types import lane_cover

    coll, sim = dblp_world
    params = SearchParams(k=5, alpha=0.8, verify_batch=8, fused="interpret")
    q = _long_query(coll, 300)
    eng = RequestEngine(coll, sim, params, partitions=3, schedule="fused")
    assert min(s.wave_operands()[2] for s in eng.partitions) == 256
    launched = _record_launches(monkeypatch)
    (r,) = eng.serve([q])
    assert r.status == "ok"
    assert all(c.nq_pad == lane_cover(300) == 384 for c in launched)
    assert any(c.c_pad < c.nq_pad for c in launched)
    _assert_exact(coll, sim, params, q, r.result)
    host = KoiosSearch(coll, sim, dataclasses.replace(params, fused="off"),
                       partitions=3).search(q)
    assert np.array_equal(host.ids, r.result.ids)
    assert np.array_equal(host.lb, r.result.lb)


def test_search_batch_k_override(small_world):
    coll, sim = small_world
    engine = KoiosSearch(coll, sim, SearchParams(k=5, alpha=0.8))
    q = sample_queries(coll, 1, seed=9)[0]
    (r3,) = engine.search_batch([q], k=3)
    assert len(r3.ids) <= 3
    assert np.array_equal(r3.ids, engine.search(q, k=3).ids)


def test_search_batch_heterogeneous_queries(small_world):
    """Mixed query lengths (different nq paddings) share one batch."""
    coll, sim = small_world
    engine = KoiosSearch(coll, sim,
                         SearchParams(k=5, alpha=0.8, verify_batch=8))
    rng = np.random.default_rng(0)
    queries = [rng.choice(coll.vocab_size, size=n, replace=False)
               .astype(np.int32) for n in (1, 3, 9, 17)]
    for q, rb in zip(queries, engine.search_batch(queries)):
        rs = engine.search(q)
        assert np.array_equal(rs.ids, rb.ids)
        assert np.array_equal(rs.lb, rb.lb)


def test_build_token_stream_batch_matches_single(small_world):
    coll, sim = small_world
    queries = sample_queries(coll, 4, seed=21)
    streams = build_token_stream_batch(queries, sim, alpha=0.8)
    for q, sb in zip(queries, streams):
        ss = build_token_stream(q, sim, alpha=0.8)
        assert np.array_equal(ss.q_pos, sb.q_pos)
        assert np.array_equal(ss.token, sb.token)
        assert np.array_equal(ss.sim, sb.sim)


def test_build_token_stream_batch_empty():
    assert build_token_stream_batch(
        [], EmbeddingSimilarity(np.eye(4, 3)), alpha=0.8) == []


def test_expand_to_events_matches_naive(small_world):
    """The vectorized posting gather equals the per-token loop."""
    coll, sim = small_world
    inv = InvertedIndex.build(coll)
    q = sample_queries(coll, 1, seed=13)[0]
    stream = build_token_stream(q, sim, 0.8)
    ev = expand_to_events(stream, inv)
    # naive per-tuple expansion oracle
    set_id, q_pos, slot, sim_v = [], [], [], []
    for qp, t, s in zip(stream.q_pos, stream.token, stream.sim):
        sets, slots = inv.postings(int(t))
        set_id.extend(sets.tolist())
        slot.extend(slots.tolist())
        q_pos.extend([qp] * len(sets))
        sim_v.extend([s] * len(sets))
    assert np.array_equal(ev.set_id, np.asarray(set_id, np.int32))
    assert np.array_equal(ev.q_pos, np.asarray(q_pos, np.int32))
    assert np.array_equal(ev.slot, np.asarray(slot, np.int64))
    assert np.array_equal(ev.sim, np.asarray(sim_v, np.float32))
    assert ev.n_tuples == len(stream)
