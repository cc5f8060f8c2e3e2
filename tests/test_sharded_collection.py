"""Sharded collection resource (DESIGN.md §5): the N-shard repository is
bit-identical to the 1-shard reference across shard counts x wave kinds x
verifiers, theta_lb stays monotone under the cross-shard bound exchange,
the ShardedCollection is the ONLY owner of collection device state
(upload-once, every consumer borrows), the device-side top-k merge tree
reproduces the stable host argsort on ties and signed zeros, placement
changes nothing, and the admission-router fleet cannot perturb any
result."""
import numpy as np
import pytest

from repro.core import (KoiosSearch, SearchParams, SearchResult, SearchStats,
                        merge_topk_batch, partition_ranges)
from repro.data import make_collection, make_embeddings, sample_queries
from repro.runtime import instrument
from repro.runtime.collection import Shard, ShardedCollection


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_caches():
    """Drop jax's accumulated executable caches before this module.

    This file compiles many fresh program variants (per-shard wave
    configs, merge trees, shard-local refinement shapes) on top of
    everything the ~250 preceding suite tests already JIT'd; on CPU
    jaxlib that accumulation has produced backend_compile segfaults
    at exactly this point in the full run (standalone the file is
    fine).  Clearing is semantically free — later tests recompile on
    demand — and keeps the suite's peak compiled-code footprint
    bounded."""
    import jax

    jax.clear_caches()


def _params(verifier="hungarian", fused=None):
    kw = dict(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
              verifier=verifier)
    if fused is not None:
        kw["fused"] = fused
    return SearchParams(**kw)


# ------------------------------------------------------- bitwise parity
@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_matches_one_shard_bitwise(small_world, verifier, shards):
    """The tentpole guarantee: N contiguous-range shards return the same
    ids and the same lb/ub floats as the unsharded repository, on both
    wave kinds (host waves, fused on-device waves) + the device-side
    top-k merge tree."""
    coll, sim = small_world
    reference = KoiosSearch(None, sim, _params(verifier, fused="off"),
                            collection=ShardedCollection.build(coll, 1))
    sharded_sc = ShardedCollection.build(coll, shards)
    assert sharded_sc.num_shards == shards
    queries = sample_queries(coll, 4, seed=5)
    ref = reference.search_batch(queries)
    for fused in ("off", "interpret"):
        sharded = KoiosSearch(None, sim, _params(verifier, fused=fused),
                              collection=sharded_sc)
        got = sharded.search_batch(queries)
        assert sharded.scheduler_stats.schedule == (
            "fused" if fused == "interpret" else "wave")
        for a, b in zip(ref, got):
            assert np.array_equal(a.ids, b.ids), fused
            assert np.array_equal(a.lb, b.lb), fused   # bit-identical
            assert np.array_equal(a.ub, b.ub), fused


def test_shard_ranges_cover_collection(small_world):
    """Shards are contiguous, non-empty, and tile [0, num_sets)."""
    coll, _ = small_world
    for n in (1, 3, 7):
        sc = ShardedCollection.build(coll, n)
        ranges = sc.shard_ranges()
        assert ranges[0][0] == 0 and ranges[-1][1] == coll.num_sets
        for (alo, ahi), (blo, bhi) in zip(ranges, ranges[1:]):
            assert ahi == blo and ahi > alo and bhi > blo
        for sid, s in enumerate(sc.shards):
            assert s.sid == sid
            assert s.coll.num_sets == ranges[sid][1] - ranges[sid][0]


# ------------------------------------------------- theta_lb monotonicity
@pytest.mark.parametrize("fused", ["off", "interpret"])
def test_theta_monotone_under_cross_shard_exchange(small_world, fused):
    """The shared theta_lb bound is only ever raised as waves cross shard
    boundaries — every exchange point in the trace is >= its
    predecessor, so cross-shard pruning is certified."""
    coll, sim = small_world
    engine = KoiosSearch(
        None, sim, _params(fused=fused),
        collection=ShardedCollection.build(coll, 4))
    queries = sample_queries(coll, 3, seed=31)
    results = engine.search_batch(queries)
    trace = engine.scheduler_stats.theta_trace
    assert len(trace) == 4                   # one row per shard wave
    for prev, cur in zip(trace, trace[1:]):
        assert np.all(cur >= prev - 1e-12), (prev, cur)
    for qi, res in enumerate(results):
        if len(res.lb) >= engine.params.k:
            assert trace[-1][qi] <= res.lb[engine.params.k - 1] + 1e-6


# ----------------------------------------------------- ownership/borrow
def test_collection_is_sole_owner_upload_once(small_world):
    """Device state lives on the resource, not on any consumer: two
    KoiosSearch instances sharing one ShardedCollection borrow the SAME
    cached per-shard arrays, and the CSR/operand/table uploads happen
    exactly once per shard no matter how many consumers search."""
    coll, sim = small_world
    sc = ShardedCollection.build(coll, 3)
    a = KoiosSearch(None, sim, _params(fused="interpret"), collection=sc)
    b = KoiosSearch(None, sim, _params(fused="interpret"), collection=sc)
    assert a.collection is sc and b.collection is sc
    assert a.partitions is sc.shards        # borrowed views, not copies
    queries = sample_queries(coll, 2, seed=9)

    with instrument.counting() as cold:
        a.search_batch(queries)
    assert cold["h2d:index_upload"] == sc.num_shards     # one per shard
    with instrument.counting() as warm:
        b.search_batch(queries)
        a.search_batch(queries)
    assert warm["h2d:index_upload"] == 0     # second consumer re-borrows

    for s in sc.shards:                      # borrows are cached objects
        assert s.wave_operands() is s.wave_operands()
        assert s.csr_arrays() is not None
    assert sc.device_bytes() > 0
    desc = sc.describe()
    assert [d["sets"] for d in desc["shards"]] == \
        [s.coll.num_sets for s in sc.shards]


def test_adopt_preserves_shard_state(small_world):
    """ShardedCollection.adopt wraps prebuilt indexes without rebuilding
    or re-uploading: existing Shards keep identity (and device cache)."""
    coll, _ = small_world
    sc = ShardedCollection.build(coll, 2)
    ops = [s.wave_operands() for s in sc.shards]
    adopted = ShardedCollection.adopt(coll, sc.shards)
    assert adopted.shards[0] is sc.shards[0]
    for s, o in zip(adopted.shards, ops):
        assert s.wave_operands() is o


# ------------------------------------------------------------ placement
def test_placed_shards_bitwise_and_pinned(small_world):
    """Placement (shard i pinned to device i%D) changes no bit: the
    placed fused run equals the unplaced reference, each placed shard's
    arrays live on its device, and uploads happen once per shard."""
    import jax

    coll, sim = small_world
    devices = jax.devices()                 # >= 1 always; CI forces 8
    params = _params(fused="interpret")
    reference = KoiosSearch(None, sim, params,
                            collection=ShardedCollection.build(coll, 1))
    placed_sc = ShardedCollection.build(coll, 4, devices=devices)
    assert placed_sc.placed
    placed = KoiosSearch(None, sim, params, collection=placed_sc)
    queries = sample_queries(coll, 3, seed=5)

    with instrument.counting() as c:
        got = placed.search_batch(queries)
    ref = reference.search_batch(queries)
    for a, b in zip(ref, got):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)
        assert np.array_equal(a.ub, b.ub)
    for s in placed_sc.shards:
        assert s.device is devices[s.sid % len(devices)]
        assert c[f"h2d:index_upload[s{s.sid}]"] == 1
        assert c[f"h2d:operand_upload[s{s.sid}]"] == 1
        for arr in s.csr_arrays():
            assert arr.devices() == {s.device}
    if len(devices) > 1:                    # theta carry hopped devices
        assert any(k.startswith("h2d:theta_hop") for k in c)
    with instrument.counting() as warm:     # steady state: no re-upload
        placed.search_batch(queries)
    assert not any(site in k for k in warm
                   for site in ("index_upload", "operand_upload",
                                "table_upload")), dict(warm)


# ------------------------------------------------------ merge tree order
def test_merge_tree_matches_stable_host_argsort():
    """Property: the device-side log-depth merge tree reproduces
    np.argsort(-lb, kind='stable')[:k] over the partition-order
    concatenation — including duplicate scores (partition order wins)
    and -0.0 vs +0.0 ties (no IEEE sign-split in the sort key)."""
    rng = np.random.default_rng(3)
    k = 4
    for trial in range(20):
        per_q = []
        for _ in range(rng.integers(1, 4)):
            parts = []
            for _ in range(rng.integers(1, 6)):
                n = int(rng.integers(0, k + 3))
                lb = rng.choice(
                    [1.0, 0.5, 0.5, 0.25, 0.0, -0.0]).astype(np.float32) \
                    * np.ones(n, np.float32) if n and trial % 3 == 0 else \
                    np.sort(rng.random(n).astype(np.float32))[::-1]
                lb = np.sort(lb)[::-1]      # partition lists arrive sorted
                parts.append(SearchResult(
                    ids=rng.integers(0, 1000, n).astype(np.int32),
                    lb=lb, ub=lb + np.float32(0.125),
                    stats=SearchStats(candidates=n)))
            per_q.append(parts)
        merged = merge_topk_batch(per_q, k)
        for rs, got in zip(per_q, merged):
            lb = np.concatenate([r.lb for r in rs] or [np.zeros(0)])
            ids = np.concatenate([r.ids for r in rs] or [np.zeros(0)])
            ub = np.concatenate([r.ub for r in rs] or [np.zeros(0)])
            order = np.argsort(-lb, kind="stable")[:k]
            assert np.array_equal(got.ids, ids[order])
            assert np.array_equal(got.lb, lb[order])
            assert np.array_equal(got.ub, ub[order])
            assert got.stats.candidates == sum(
                r.stats.candidates for r in rs)


# ------------------------------------------- partition_ranges degeneracy
def test_token_partition_ranges_never_empty():
    """Regression: the token-balanced splitter used to emit empty
    partitions when ``partitions`` approached the set count (greedy cuts
    collapse under size skew); every partition must hold >= 1 set."""
    skew = np.array([100, 1, 1, 1, 1])
    for p in (1, 2, 3, 4, 5, 6, 9):
        bounds = partition_ranges(skew, p, by="tokens")
        assert bounds[0] == 0 and bounds[-1] == len(skew)
        assert np.all(np.diff(bounds) > 0), (p, bounds)
        assert len(bounds) == min(p, len(skew)) + 1
    rng = np.random.default_rng(0)
    for _ in range(25):
        n = int(rng.integers(1, 12))
        sizes = rng.integers(1, 60, n)
        sizes[rng.integers(0, n)] = 500     # one dominating set
        p = int(rng.integers(1, n + 3))
        bounds = partition_ranges(sizes, p, by="tokens")
        assert bounds[0] == 0 and bounds[-1] == n
        assert np.all(np.diff(bounds) > 0), (sizes, p, bounds)


def test_build_drops_empty_shards():
    """shards > num_sets degenerates to one set per shard (by='sets'
    ranges past the end are dropped, never emitted empty)."""
    coll = make_collection(num_sets=3, vocab_size=50, avg_size=4,
                           max_size=8, seed=1)
    sc = ShardedCollection.build(coll, 8)
    assert sc.num_shards == 3
    assert all(s.coll.num_sets == 1 for s in sc.shards)


# -------------------------------------------------------- router parity
def test_admission_router_cannot_perturb_results(small_world):
    """The replica fleet behind the admission router returns, in global
    submission order, responses bit-identical to a one-shot
    search_batch over the same shared collection."""
    from repro.runtime.engine import AdmissionRouter

    coll, sim = small_world
    params = _params()
    sc = ShardedCollection.build(coll, 2)
    one_shot = KoiosSearch(None, sim, params, collection=sc)
    router = AdmissionRouter(None, sim, params, replicas=3, collection=sc)
    assert all(e.collection is sc for e in router.engines)

    queries = sample_queries(coll, 7, seed=13)
    ref = one_shot.search_batch(queries)
    responses = router.serve(queries)
    assert [r.rid for r in responses] == list(range(len(queries)))
    for r, a in zip(responses, ref):
        assert np.array_equal(r.result.ids, a.ids)
        assert np.array_equal(r.result.lb, a.lb)
    s = router.summary()
    assert s["replicas"] == 3
    assert s["requests"] == len(queries)
    assert sum(p["requests"] for p in s["per_replica"]) == len(queries)
    # least-pending + round-robin: an idle fleet spreads arrivals
    assert all(p["requests"] > 0 for p in s["per_replica"])
