"""Continuous-batching request engine (DESIGN.md §3.2): staggered
arrivals with mid-flight joins are bit-identical to the one-shot
``search_batch`` path across wave kinds x partitions x verifiers; the
stream cache serves bit-identical streams on hits and evicts LRU; the
engine reports true per-request lifecycle timings."""
import dataclasses

import numpy as np
import pytest

from repro.core import (KoiosSearch, SearchParams, TokenStreamCache,
                        build_token_stream_batch,
                        build_token_stream_batch_cached)
from repro.data import sample_queries
from repro.launch.serve import SearchServer
from repro.runtime.engine import RequestEngine


def _fake_clock():
    """Deterministic virtual clock: (now, advance, sleep)."""
    t = [1000.0]
    return (lambda: t[0],
            lambda dt: t.__setitem__(0, t[0] + dt),
            lambda dt: t.__setitem__(0, t[0] + dt))


def _params(verifier="hungarian", fused=False):
    return SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                        verifier=verifier,
                        fused="interpret" if fused else "auto")


def _host(params):
    """The same parameters on host waves: the one-shot reference."""
    return dataclasses.replace(params, fused="off")


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("partitions", [1, 4])
@pytest.mark.parametrize("schedule", ["wave", "fused"])
def test_engine_staggered_bitwise_vs_one_shot(small_world, verifier,
                                              partitions, schedule):
    """The tentpole guarantee: requests admitted mid-flight (while other
    requests are partway through their partition waves) produce results
    bit-identical to the one-shot batch path — per-query theta carries
    plus schedule-invariant row numerics make any join point sound."""
    coll, sim = small_world
    params = _params(verifier, fused=(schedule == "fused"))
    queries = sample_queries(coll, 5, seed=5)
    one_shot = KoiosSearch(coll, sim, _host(params), partitions=partitions)
    ref = one_shot.search_batch(queries)

    clock, advance, sleep = _fake_clock()
    eng = RequestEngine(coll, sim, params, partitions=partitions,
                        schedule=schedule, clock=clock, sleep=sleep)
    assert eng.schedule == schedule          # gate really resolved
    for q in queries[:3]:
        eng.submit(q)
    resp = list(eng.step())                  # first cohort starts
    advance(0.25)
    for q in queries[3:]:                    # join mid-flight
        eng.submit(q)
    while eng.pending():
        advance(0.01)
        resp.extend(eng.step())
    resp.sort(key=lambda r: r.rid)

    assert len(resp) == len(queries)
    for r, a in zip(resp, ref):
        assert np.array_equal(r.result.ids, a.ids)
        assert np.array_equal(r.result.lb, a.lb)   # bit-identical floats
        assert np.array_equal(r.result.ub, a.ub)
    # the join really was mid-flight: the late cohort's first wave ran
    # strictly after the early cohort's (it joined later) yet strictly
    # before the early cohort responded (no head-of-line blocking) —
    # so the plan ran more waves than one lock-step pass
    if partitions > 1:
        assert eng.plan.stats.waves > partitions
        early = [t for t in eng.counters.traces if t.rid < 3]
        late = [t for t in eng.counters.traces if t.rid >= 3]
        assert min(t.t_first_wave for t in late) \
            > min(t.t_first_wave for t in early)
        assert min(t.t_first_wave for t in late) \
            < max(t.t_respond for t in early)
    # every request's lifecycle is fully accounted
    s = eng.summary()
    assert s["requests"] == len(queries)
    assert s["steps"] >= 1
    assert s["stream_cache"]["misses"] >= 1


def test_engine_serve_matches_every_one_shot_schedule(small_world):
    """engine == one-shot host waves == one-shot fused waves, bitwise."""
    coll, sim = small_world
    params = _params()
    queries = sample_queries(coll, 6, seed=23)
    eng = RequestEngine(coll, sim, params, partitions=3)
    resp = eng.serve(queries)
    for fused in ("off", "interpret"):
        one_shot = KoiosSearch(
            coll, sim, dataclasses.replace(params, fused=fused),
            partitions=3)
        for r, a in zip(resp, one_shot.search_batch(queries)):
            assert np.array_equal(r.result.ids, a.ids), fused
            assert np.array_equal(r.result.lb, a.lb), fused


def test_stream_cache_hit_parity(small_world):
    """Cached builds are bit-identical to uncached builds — on the miss
    path, the hit path, and duplicate queries within one call."""
    coll, sim = small_world
    queries = sample_queries(coll, 4, seed=3)
    alpha = 0.8
    ref = build_token_stream_batch(queries, sim, alpha)

    cache = TokenStreamCache()
    miss = build_token_stream_batch_cached(queries, sim, alpha, cache)
    hit = build_token_stream_batch_cached(queries, sim, alpha, cache)
    dup = build_token_stream_batch_cached(
        [queries[0], queries[1], queries[0]], sim, alpha, cache)
    for got in (miss, hit):
        for s, r in zip(got, ref):
            assert np.array_equal(s.q_pos, r.q_pos)
            assert np.array_equal(s.token, r.token)
            assert np.array_equal(s.sim, r.sim)    # bit-identical floats
    assert np.array_equal(dup[2].sim, ref[0].sim)
    assert cache.misses == len(queries)
    assert cache.hits == len(queries) + 3          # full rerun + dup call
    assert cache.stats()["hit_rate"] == pytest.approx(
        cache.hits / (cache.hits + cache.misses))

    # a fresh-but-equal query array is the same key (value semantics)
    again = build_token_stream_batch_cached(
        [np.array(queries[0], np.int32)], sim, alpha, cache)
    assert np.array_equal(again[0].sim, ref[0].sim)
    assert cache.misses == len(queries)


def test_stream_cache_eviction_lru(small_world):
    """The byte budget bounds the cache; the LRU entry is evicted first
    and an evicted key rebuilds (miss) to a bit-identical stream."""
    coll, sim = small_world
    q = sample_queries(coll, 3, seed=9)
    alpha = 0.8
    probe = TokenStreamCache()
    streams = build_token_stream_batch_cached(q, sim, alpha, probe)
    sizes = [TokenStreamCache._nbytes(s) for s in streams]
    # budget holds any two of the three streams but never all three
    cache = TokenStreamCache(max_bytes=sum(sizes) - min(sizes) // 2 - 1)
    k0 = cache.key(q[0], alpha, sim)

    build_token_stream_batch_cached([q[0], q[1]], sim, alpha, cache)
    ref0 = build_token_stream_batch(q[:1], sim, alpha)[0]
    assert cache.contains(k0) and len(cache) == 2
    assert cache.bytes == sizes[0] + sizes[1] <= cache.max_bytes

    build_token_stream_batch_cached([q[1]], sim, alpha, cache)  # q0 -> LRU
    build_token_stream_batch_cached([q[2]], sim, alpha, cache)  # evicts q0
    assert cache.evictions == 1
    assert not cache.contains(k0)
    assert len(cache) == 2
    assert cache.bytes == sizes[1] + sizes[2]
    assert cache.describe()["bytes"] == cache.bytes

    misses = cache.misses
    rebuilt = build_token_stream_batch_cached([q[0]], sim, alpha, cache)
    assert cache.misses == misses + 1
    assert np.array_equal(rebuilt[0].sim, ref0.sim)
    assert np.array_equal(rebuilt[0].token, ref0.token)


def test_engine_deadlines_order_admission(small_world):
    """Earliest-deadline-first admission: with room for one request per
    wave, the tighter deadline is served first; deadline outcomes are
    reported per request."""
    coll, sim = small_world
    params = _params()
    q = sample_queries(coll, 2, seed=31)
    clock, advance, sleep = _fake_clock()
    eng = RequestEngine(coll, sim, params, partitions=1,
                        max_wave_requests=1, clock=clock, sleep=sleep)
    eng.submit(q[0], deadline=clock() + 1e9)
    eng.submit(q[1], deadline=clock() + 0.5)
    resp = eng.drain()
    assert [r.rid for r in resp] == [1, 0]
    assert resp[0].deadline_met is not None


def test_serve_batch_reports_true_per_request_latencies(small_world):
    """The serve_batch satellite: per-request admit->respond latencies
    from the engine's instrumentation — not one amortized number —
    plus queue/wave/cache attribution per response."""
    coll, sim = small_world
    params = _params()
    server = SearchServer(coll, sim, params, partitions=2)
    queries = sample_queries(coll, 4, seed=41)
    out = server.serve_batch(queries)
    assert len(out) == len(queries)
    for r in out:
        assert r["latency_s"] >= 0.0
        assert r["queue_s"] >= 0.0
        assert r["waves"] >= 1
        assert "stream_cache_hit" in r
    s = server.engine.summary()
    assert s["requests"] == len(queries)
    assert s["mean_latency_s"] >= 0.0
    # repeated batch: streams now come from the cache
    server.serve_batch(queries)
    assert server.engine.stream_cache.hits >= len(queries)
    # per-query baseline path still serves identical results
    pq = server.serve_batch(queries, batched=False)
    for a, b in zip(out, pq):
        assert a["ids"] == b["ids"]
        assert a["scores"] == b["scores"]


def test_engine_warmup_resets_counters(small_world):
    coll, sim = small_world
    eng = RequestEngine(coll, sim, _params(), partitions=2)
    queries = sample_queries(coll, 2, seed=17)
    eng.warmup(queries)
    assert eng.counters.traces == [] and eng.counters.steps == 0
    assert len(eng.stream_cache) >= 1        # warmup populated the cache
    resp = eng.serve(queries)
    assert all(r.stream_hit for r in resp)   # ... so serving hits it
    assert eng.summary()["requests"] == len(queries)


def test_plan_query_ring_bounded(small_world):
    """Long-lived engines: many admit/respond cycles keep the plan's
    query list bounded (``ExecutionPlan.retire_tiles`` compaction ring,
    DESIGN.md §9 item 9), qi-indexed engine state follows the remap,
    and results stay bit-identical to the one-shot path throughout."""
    coll, sim = small_world
    params = _params()
    clock, advance, sleep = _fake_clock()
    eng = RequestEngine(coll, sim, params, partitions=2,
                        clock=clock, sleep=sleep)
    eng.plan.compact_min = 8             # trigger the ring at test scale
    queries = sample_queries(coll, 6, seed=21)
    one_shot = KoiosSearch(coll, sim, _host(params), partitions=2)
    ref = one_shot.search_batch(queries)

    served, max_len = 0, 0
    for cycle in range(12):
        # overlapping submissions: half joins while the other half is
        # mid-flight, so compaction interleaves with live requests
        for q in queries[:3]:
            eng.submit(q)
        resp = list(eng.step())
        for q in queries[3:]:
            eng.submit(q)
        while eng.pending():
            advance(0.01)
            resp.extend(eng.step())
            max_len = max(max_len, len(eng.plan.queries))
        for r in resp:
            a = ref[r.rid % len(queries)]
            assert np.array_equal(r.result.ids, a.ids)
            assert np.array_equal(r.result.lb, a.lb)
        served += len(resp)
    assert served == 12 * len(queries)
    # 72 requests served; without the ring the plan list would hold all
    # of them — with it, the list stays near the live-request ceiling
    assert max_len <= 2 * eng.plan.compact_min, max_len
    assert len(eng.plan.queries) <= eng.plan.compact_min
    assert eng.plan.tiles == []          # everything retired
