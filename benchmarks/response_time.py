"""Paper Table III: response time + memory, KOIOS vs Baseline/Baseline+.

Also covers the SilkMoth comparison mode (--sim ngram): the same engine
with character n-gram Jaccard similarity (KOIOS is similarity-agnostic —
§VIII-B).

Batched-serving A/B (``--batched`` / ``--per-query``): times the fused
multi-query pipeline (``search_partition_batch``) against the per-query
loop on the same query batch, asserting identical top-k results:

    PYTHONPATH=src python -m benchmarks.response_time --batched

Scale-out A/B (``--partitions N --overlap``): times the overlapped
partition scheduler (async refinement dispatch, global verify queue,
bidirectional theta_lb feedback) against the sequential running-max
partition loop, asserting bit-identical results:

    PYTHONPATH=src python -m benchmarks.response_time --partitions 4 --overlap

Fused-wave A/B (``--fused``): times the on-device wave schedule (one
device program per partition wave — refinement chunk scans + compaction +
the first R verification rounds fused, DESIGN.md §3) against the
host-driven overlap schedule, counting host<->device dispatches/transfers
with ``repro.runtime.instrument`` and asserting bit-identical results:

    PYTHONPATH=src python -m benchmarks.response_time --fused --partitions 4

Request-engine A/B (``--engine``): replays a staggered-arrival trace
through the continuous-batching engine (admission queue, mid-flight
joins, LRU stream cache — DESIGN.md §3.2) against the per-batch serving
loop that waits for each fixed batch to fill, comparing TRUE mean
per-request (admit->respond) latency and asserting hash-identical
results:

    PYTHONPATH=src python -m benchmarks.response_time --engine --partitions 4

Sharded-collection A/B (``--shards N`` / ``--sharded``): builds the same
logical repository as a 1-shard and an N-shard
:class:`~repro.runtime.collection.ShardedCollection` (``--place`` pins
shard i round-robin to ``jax.devices()[i]``), runs the fused schedule +
device-side top-k merge tree over both, asserts bit-identical results
(equal hash), and attributes per-shard wave dispatches / uploads /
theta-carry hops from the sid-tagged instrument event stream:

    PYTHONPATH=src python -m benchmarks.response_time --shards 4 --place

Every A/B invocation also merges its record into
``BENCH_response_time.json`` under ``records[<mode>]`` (per-mode
latencies + a hash of the results) so CI accumulates the perf
trajectory of every mode as one artifact; ``--json ''`` disables.
"""
from __future__ import annotations

import argparse
import hashlib
import json

import numpy as np

from repro.core import (NGramJaccardSimilarity, SearchParams,
                        baseline_plus_topk, baseline_topk, search_partition,
                        search_partition_batch)
from repro.data import sample_queries
from repro.runtime.compile_cache import enable_compile_cache

from .common import index_for, memory_footprint_bytes, timed, world


def _ngram_incidence(vocab_size: int, dim: int = 512, seed: int = 0):
    """Hashed 3-gram incidence stand-in (tokens are synthetic ids; we hash
    pseudo-spellings)."""
    rng = np.random.default_rng(seed)
    inc = np.zeros((vocab_size, dim), np.float32)
    for t in range(vocab_size):
        g = rng.integers(0, dim, size=6)      # ~6 3-grams per token
        inc[t, g] = 1.0
    return inc


def run(datasets=("dblp", "opendata", "twitter", "wdc"), n_queries=2,
        k=10, alpha=0.8, sim_kind="cosine", include_baseline=True):
    rows = []
    params = SearchParams(k=k, alpha=alpha)
    for ds in datasets:
        coll, sim = world(ds)
        if sim_kind == "ngram":
            sim = NGramJaccardSimilarity(_ngram_incidence(coll.vocab_size))
        index = index_for(ds)
        queries = sample_queries(coll, n_queries, seed=11)
        # warm the jit caches (the paper's timings exclude setup; pow2
        # padding makes later queries reuse these compilations)
        if queries:
            search_partition(index, queries[0], sim, params)
            if include_baseline:
                baseline_topk(index, queries[0], sim, params)
        tk = tb = tbp = 0.0
        match_k = match_b = 0
        for q in queries:
            rk, dt = timed(search_partition, index, q, sim, params)
            tk += dt
            match_k += rk.stats.exact_matches
            if include_baseline:
                rb, dt = timed(baseline_topk, index, q, sim, params)
                tb += dt
                match_b += rb.stats.exact_matches
                rbp, dt = timed(baseline_plus_topk, index, q, sim, params)
                tbp += dt
                # sanity: identical score multisets
                assert np.allclose(np.sort(rk.lb), np.sort(rb.lb), atol=1e-3)
        n = max(len(queries), 1)
        mem = memory_footprint_bytes(ds, int(np.mean(
            [len(q) for q in queries])) if queries else 1)
        rows.append({
            "dataset": ds, "sim": sim_kind, "queries": n,
            "koios_s": tk / n,
            "baseline_s": tb / n if include_baseline else None,
            "baseline_plus_s": tbp / n if include_baseline else None,
            "speedup": (tb / tk) if include_baseline and tk else None,
            "em_koios": match_k / n,
            "em_baseline": match_b / n if include_baseline else None,
            "mem_mb": mem["total"] / 1e6,
        })
    return rows


def run_ab(dataset="opendata", batch_size=8, k=10, alpha=0.8,
           verifier="hungarian", repeats=3):
    """Batched vs per-query A/B on one query batch; identical-results check.

    Both paths are warmed (jit caches), then each is timed ``repeats``
    times over the same ``batch_size`` queries; reports mean seconds per
    query and the batched-path speedup.
    """
    params = SearchParams(k=k, alpha=alpha, verifier=verifier)
    _, sim = world(dataset)
    index = index_for(dataset)
    queries = sample_queries(index.coll, batch_size, seed=11)
    zeros = [0.0] * len(queries)

    def per_query():
        return [search_partition(index, q, sim, params) for q in queries]

    def batched():
        return search_partition_batch(index, queries, sim, params, zeros)

    r_pq, _ = timed(per_query)       # warm both paths before timing
    r_b, _ = timed(batched)
    for a, b in zip(r_pq, r_b):
        assert np.array_equal(a.ids, b.ids) and np.array_equal(a.lb, b.lb), \
            "batched path diverged from per-query results"

    t_pq = min(timed(per_query)[1] for _ in range(repeats))
    t_b = min(timed(batched)[1] for _ in range(repeats))
    n = len(queries)
    return {
        "dataset": dataset, "batch_size": n, "verifier": verifier,
        "per_query_s": t_pq / n, "batched_s": t_b / n,
        "speedup": t_pq / t_b if t_b else float("inf"),
        "result_hash": result_hash(r_b),
        "identical_topk": True,
    }


def run_partition_ab(dataset="opendata", partitions=4, batch_size=8, k=10,
                     alpha=0.8, verifier="hungarian", repeats=3):
    """Overlapped scheduler vs sequential partition loop at P partitions.

    Both arms run the same engine (same plan decomposition, same shared
    verifier pool); the A/B isolates the scheduler's drive order —
    overlapped refinement dispatch + the global cross-partition queue +
    bidirectional theta_lb feedback vs the pre-scheduler running-max host
    loop.  Results are asserted bit-identical; reports mean seconds per
    query and the overlap speedup.
    """
    from repro.core import KoiosSearch

    params = SearchParams(k=k, alpha=alpha, verifier=verifier)
    coll, sim = world(dataset)
    engine = KoiosSearch(coll, sim, params, partitions=partitions)
    queries = sample_queries(coll, batch_size, seed=11)

    def sequential():
        return engine.search_batch(queries, schedule="sequential")

    def overlap():
        return engine.search_batch(queries, schedule="overlap")

    r_seq, _ = timed(sequential)     # warm both paths before timing
    r_ovl, _ = timed(overlap)
    st = engine.scheduler_stats
    for a, b in zip(r_seq, r_ovl):
        assert np.array_equal(a.ids, b.ids) and np.array_equal(a.lb, b.lb), \
            "overlapped schedule diverged from the sequential partition loop"

    t_seq = min(timed(sequential)[1] for _ in range(repeats))
    t_ovl = min(timed(overlap)[1] for _ in range(repeats))
    n = len(queries)
    return {
        "dataset": dataset, "partitions": partitions, "batch_size": n,
        "verifier": verifier,
        "sequential_s": t_seq / n, "overlap_s": t_ovl / n,
        "speedup": t_seq / t_ovl if t_ovl else float("inf"),
        "bound_raises": st.bound_raises,
        "backward_raises": st.backward_raises,
        "result_hash": result_hash(r_ovl),
        "identical_topk": True,
    }


def result_hash(results) -> str:
    """Stable digest of a list of SearchResults (ids + score bits)."""
    h = hashlib.sha256()
    for r in results:
        h.update(np.ascontiguousarray(r.ids).tobytes())
        h.update(np.ascontiguousarray(r.lb).tobytes())
    return h.hexdigest()[:16]


def run_fused_ab(dataset="opendata", partitions=4, batch_size=8, k=10,
                 alpha=0.8, verifier="hungarian", repeats=7, fused="auto"):
    """Fused on-device wave schedule vs host-driven overlap at P partitions.

    Both arms run the identical plan decomposition; the A/B isolates what
    the wave program eliminates — per-tile refinement dispatch +
    materialization and the first R rounds' pairwise/solver round-trips.
    Host<->device dispatches and transfers are counted via
    ``repro.runtime.instrument``; results are asserted bit-identical.
    ``fused='interpret'`` runs the wave off the chip."""
    from repro.core import KoiosSearch
    from repro.runtime import instrument

    params = SearchParams(k=k, alpha=alpha, verifier=verifier, fused=fused)
    coll, sim = world(dataset)
    engine = KoiosSearch(coll, sim, params, partitions=partitions)
    queries = sample_queries(coll, batch_size, seed=11)

    def overlap():
        return engine.search_batch(queries, schedule="overlap")

    def fused():
        return engine.search_batch(queries, schedule="fused")

    r_ovl, _ = timed(overlap)        # warm both paths before timing
    r_fus, _ = timed(fused)
    assert engine.scheduler_stats.schedule == "fused", \
        "fused schedule unavailable (provider or backend gate)"
    for a, b in zip(r_ovl, r_fus):
        assert np.array_equal(a.ids, b.ids) and np.array_equal(a.lb, b.lb), \
            "fused wave schedule diverged from the overlap schedule"

    counts = {}
    for name, fn in (("overlap", overlap), ("fused", fused)):
        with instrument.counting() as c:
            fn()
        counts[name] = instrument.totals(c)
    t_ovl = min(timed(overlap)[1] for _ in range(repeats))
    t_fus = min(timed(fused)[1] for _ in range(repeats))
    n = len(queries)
    st = engine.scheduler_stats
    return {
        "dataset": dataset, "partitions": partitions, "batch_size": n,
        "verifier": verifier,
        "overlap_s": t_ovl / n, "fused_s": t_fus / n,
        "speedup": t_ovl / t_fus if t_fus else float("inf"),
        "overlap_transfers": counts["overlap"]["total"],
        "fused_transfers": counts["fused"]["total"],
        "waves": st.waves, "device_rounds": st.device_rounds,
        "result_hash": result_hash(r_fus),
        "identical_topk": True,
    }


def run_sharded_ab(dataset="opendata", shards=4, batch_size=8, k=10,
                   alpha=0.8, verifier="hungarian", repeats=3,
                   place=False, fused="auto"):
    """Sharded collection resource vs the 1-shard reference repository.

    Builds the SAME logical repository twice as a
    :class:`~repro.runtime.collection.ShardedCollection` — once at one
    shard (the degenerate reference) and once at ``shards`` contiguous
    set ranges, optionally placed round-robin over ``jax.devices()``
    (``--place``).  Both arms run the fused wave schedule and the
    device-side top-k merge tree; results are asserted bit-identical
    (equal ``result_hash``), and per-shard wave dispatches / uploads /
    theta-carry hops are attributed via the sid-tagged event stream of
    ``repro.runtime.instrument``."""
    import jax

    from repro.core import KoiosSearch
    from repro.runtime import instrument
    from repro.runtime.collection import ShardedCollection

    params = SearchParams(k=k, alpha=alpha, verifier=verifier, fused=fused)
    coll, sim = world(dataset)
    devices = jax.devices() if place else None
    reference = KoiosSearch(None, sim, params,
                            collection=ShardedCollection.build(coll, 1))
    sharded = KoiosSearch(
        None, sim, params,
        collection=ShardedCollection.build(coll, shards, devices=devices))
    queries = sample_queries(coll, batch_size, seed=11)

    def one_shard():
        return reference.search_batch(queries, schedule="fused")

    def n_shard():
        return sharded.search_batch(queries, schedule="fused")

    with instrument.counting() as c_cold:    # first borrow = the uploads
        r_sh, _ = timed(n_shard)
    r_ref, _ = timed(one_shard)
    assert sharded.scheduler_stats.schedule == "fused", \
        "fused schedule unavailable (provider or backend gate)"
    for a, b in zip(r_ref, r_sh):
        assert np.array_equal(a.ids, b.ids) and np.array_equal(a.lb, b.lb), \
            "sharded collection diverged from the 1-shard reference"
    ref_hash, sh_hash = result_hash(r_ref), result_hash(r_sh)
    assert ref_hash == sh_hash, "result hash diverged across shard counts"

    counts = {}
    for name, fn in (("one_shard", one_shard), ("sharded", n_shard)):
        with instrument.counting() as c:
            fn()
        counts[name] = instrument.totals(c)
    with instrument.counting() as c_warm:    # steady-state sharded arm
        n_shard()

    def per_shard(counter):
        """sid-tagged events grouped per shard: {'s0': {tag: n}, ...}."""
        out = {}
        for tag, n in sorted(counter.items()):
            if "[s" not in tag:
                continue
            site, sid = tag.rsplit("[", 1)
            out.setdefault(sid.rstrip("]"), {})[site] = n
        return out

    t_ref = min(timed(one_shard)[1] for _ in range(repeats))
    t_sh = min(timed(n_shard)[1] for _ in range(repeats))
    n = len(queries)
    desc = sharded.collection.describe()
    return {
        "dataset": dataset, "shards": sharded.collection.num_shards,
        "batch_size": n, "verifier": verifier,
        "placed": sharded.collection.placed,
        "devices": len(set(s["device"] for s in desc["shards"]
                           if s["device"])),
        "one_shard_s": t_ref / n, "sharded_s": t_sh / n,
        "speedup": t_ref / t_sh if t_sh else float("inf"),
        "one_shard_transfers": counts["one_shard"]["total"],
        "sharded_transfers": counts["sharded"]["total"],
        "upload_events": per_shard(c_cold),
        "steady_state_events": per_shard(c_warm),
        "shard_sets": [s["sets"] for s in desc["shards"]],
        "device_bytes": desc["device_bytes"],
        "result_hash": sh_hash,
        "identical_topk": True,
    }


def run_engine_ab(dataset="opendata", partitions=4, batch_size=8,
                  n_requests=16, unique=8, stagger_ms=25.0, k=10,
                  alpha=0.8, verifier="hungarian", repeats=3):
    """Continuous-batching engine vs the per-batch serving loop under a
    staggered-arrival trace.

    Both arms see the same trace: request i arrives ``stagger_ms`` after
    request i-1, and requests repeat each of ``unique`` distinct queries
    (the stream-cache story).  The baseline is the pre-engine serving
    loop — wait until a fixed ``batch_size`` batch has fully arrived,
    run it one-shot, repeat — so every request's latency includes its
    wait for the batch to fill.  The engine admits each request on
    arrival and coalesces whatever is queued into the next wave
    (mid-flight joins).  Mean per-request (admit->respond) latency is
    the headline; results are asserted hash-identical across both arms
    and the warmed one-shot reference."""
    import time as _time

    from repro.core import KoiosSearch
    from repro.runtime.engine import RequestEngine

    params = SearchParams(k=k, alpha=alpha, verifier=verifier)
    coll, sim = world(dataset)
    one_shot = KoiosSearch(coll, sim, params, partitions=partitions)
    indexes = one_shot.partitions       # engines reuse the same indexes

    base = sample_queries(coll, unique, seed=11)
    reqs = [base[i % unique] for i in range(n_requests)]
    stagger = stagger_ms / 1e3

    # Warm both paths' jit caches and pin the reference results.  The
    # engine's steady-state shapes depend on cohort size (pow2-padded
    # solver rows), so warm every pow2 cohort the staggered trace can
    # coalesce — after this, the sweep itself compiles nothing
    # (tests/test_recompile.py asserts the same invariant).
    ref = one_shot.search_batch(reqs, schedule="overlap")
    warm_engine = RequestEngine(coll, sim, params, indexes=indexes)
    warm_engine.warmup(reqs)
    for r, a in zip(warm_engine.serve(reqs), ref):
        assert np.array_equal(r.result.ids, a.ids) \
            and np.array_equal(r.result.lb, a.lb), \
            "engine diverged from the one-shot path"
    ref_hash = result_hash(ref)

    def engine_run():
        eng = RequestEngine(coll, sim, params, indexes=indexes)
        t0 = eng.clock()
        for i, q in enumerate(reqs):
            eng.submit(q, arrival=t0 + i * stagger)
        resp = sorted(eng.drain(), key=lambda r: r.rid)
        return eng, [r.result for r in resp], [r.latency_s for r in resp]

    def loop_run():
        results, lats = [], []
        t0 = _time.monotonic()
        arrivals = [i * stagger for i in range(n_requests)]
        for lo in range(0, n_requests, batch_size):
            hi = min(lo + batch_size, n_requests)
            wait = (t0 + arrivals[hi - 1]) - _time.monotonic()
            if wait > 0:                 # batch waits for its last member
                _time.sleep(wait)
            rs = one_shot.search_batch(reqs[lo:hi], schedule="overlap")
            t_done = _time.monotonic()
            results.extend(rs)
            lats.extend(t_done - (t0 + arrivals[i])
                        for i in range(lo, hi))
        return results, lats

    eng_means, loop_means = [], []
    eng = None
    for _ in range(repeats):
        eng, eng_results, eng_lats = engine_run()
        loop_results, loop_lats = loop_run()
        assert result_hash(eng_results) == ref_hash, \
            "engine results diverged under the staggered trace"
        assert result_hash(loop_results) == ref_hash
        eng_means.append(sum(eng_lats) / len(eng_lats))
        loop_means.append(sum(loop_lats) / len(loop_lats))
    t_eng, t_loop = min(eng_means), min(loop_means)
    summary = eng.summary()
    return {
        "dataset": dataset, "partitions": partitions,
        "batch_size": batch_size, "n_requests": n_requests,
        "unique_queries": unique, "stagger_ms": stagger_ms,
        "verifier": verifier,
        "engine_s": t_eng, "batch_loop_s": t_loop,
        "speedup": t_loop / t_eng if t_eng else float("inf"),
        "cache_hit_rate": summary["stream_cache"]["hit_rate"],
        "mean_queue_depth": summary["mean_queue_depth"],
        "engine_waves": summary["scheduler"]["waves"],
        "result_hash": ref_hash,
        "identical_topk": True,
    }


def write_bench_json(record: dict, path: str, mode: str) -> None:
    """BENCH_response_time.json — the perf-trajectory artifact CI uploads.

    One document keyed by mode: each A/B invocation merges its record
    under ``records[mode]`` instead of clobbering the file, so the
    trajectory of every mode (``batched_ab``/``partition_ab``/
    ``fused_ab``/``engine_ab``/``sharded_ab``/``suite``) stays
    comparable across PRs.
    Legacy single-mode documents are migrated on first merge."""
    if not path:
        return
    doc = {"benchmark": "response_time", "records": {}}
    try:
        with open(path) as f:
            prev = json.load(f)
        if "records" in prev:
            doc["records"] = prev["records"]
        elif prev.get("mode"):           # legacy single-mode layout
            legacy = {k: v for k, v in prev.items()
                      if k not in ("benchmark", "mode")}
            doc["records"][prev["mode"]] = legacy
    except (OSError, ValueError):
        pass
    doc["records"][mode] = record
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"[bench] wrote {path} (mode={mode}, "
          f"{len(doc['records'])} records)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--batched", action="store_true",
                      help="A/B the fused multi-query path (headline row)")
    mode.add_argument("--per-query", action="store_true",
                      help="A/B with the per-query loop as the headline row")
    mode.add_argument("--overlap", action="store_true",
                      help="A/B the overlapped partition scheduler vs the "
                           "sequential partition loop (use --partitions)")
    mode.add_argument("--fused", action="store_true",
                      help="A/B the fused on-device wave schedule vs the "
                           "overlap schedule (use --partitions; off the "
                           "chip add --interpret)")
    mode.add_argument("--engine", action="store_true",
                      help="A/B the continuous-batching request engine vs "
                           "the per-batch serving loop under a staggered-"
                           "arrival trace (true per-request latencies, "
                           "stream-cache hit rate)")
    mode.add_argument("--sharded", action="store_true",
                      help="A/B the sharded collection resource vs the "
                           "1-shard reference repository (bit-identical "
                           "top-k, per-shard transfer attribution; "
                           "implied by --shards)")
    ap.add_argument("--dataset", default=None,
                    help="restrict to one dataset (A/B default: opendata; "
                         "table mode default: all four)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="A/B modes only")
    ap.add_argument("--partitions", type=int, default=4,
                    help="--overlap A/B only: repository partition count")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count for the sharded-collection A/B "
                         "(selects --sharded mode; default 4)")
    ap.add_argument("--place", action="store_true",
                    help="--sharded A/B only: pin shard i round-robin "
                         "to jax.devices()[i] (theta carry hops "
                         "device-to-device)")
    ap.add_argument("--n-requests", type=int, default=16,
                    help="--engine A/B only: trace length")
    ap.add_argument("--stagger-ms", type=float, default=25.0,
                    help="--engine A/B only: inter-arrival gap")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--verifier", default="hungarian",
                    choices=["hungarian", "auction", "hybrid"],
                    help="A/B modes only")
    ap.add_argument("--interpret", action="store_true",
                    help="--fused/--sharded A/Bs: run the wave programs "
                         "off the chip, Pallas kernels in interpret mode")
    ap.add_argument("--json", default="BENCH_response_time.json",
                    help="perf-artifact path for A/B modes ('' disables)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    fused = "interpret" if args.interpret else "auto"

    if args.sharded or args.shards is not None:
        r = run_sharded_ab(args.dataset or "opendata",
                           args.shards or 4, args.batch_size,
                           k=args.k, verifier=args.verifier,
                           place=args.place, fused=fused)
        print("dataset,arm,shards,devices,batch_size,"
              "mean_latency_per_query_s,speedup_vs_one_shard,"
              "transfers,result_hash,identical_topk")
        for name, shards, lat, sp, tr in (
                ("sharded", r["shards"], r["sharded_s"], r["speedup"],
                 r["sharded_transfers"]),
                ("one-shard", 1, r["one_shard_s"], 1.0,
                 r["one_shard_transfers"])):
            print(f"{r['dataset']},{name},{shards},{r['devices']},"
                  f"{r['batch_size']},{lat:.4f},{sp:.2f},{tr},"
                  f"{r['result_hash']},{r['identical_topk']}")
        for sid in sorted(r["upload_events"]):
            up = r["upload_events"][sid]
            steady = r["steady_state_events"].get(sid, {})
            print(f"  [{sid}] uploads={ {t.split(':', 1)[1]: n for t, n in up.items()} } "
                  f"steady_waves={steady.get('h2d:wave_dispatch', 0)} "
                  f"theta_hops={steady.get('h2d:theta_hop', 0)}")
        write_bench_json(r, args.json, "sharded_ab")
        return 0

    if args.engine:
        r = run_engine_ab(args.dataset or "opendata", args.partitions,
                          args.batch_size, n_requests=args.n_requests,
                          stagger_ms=args.stagger_ms, k=args.k,
                          verifier=args.verifier)
        print("dataset,mode,partitions,n_requests,stagger_ms,"
              "mean_latency_per_request_s,speedup_vs_batch_loop,"
              "cache_hit_rate,mean_queue_depth,result_hash,identical_topk")
        for name, lat, sp in (
                ("engine", r["engine_s"], r["speedup"]),
                ("batch-loop", r["batch_loop_s"], 1.0)):
            print(f"{r['dataset']},{name},{r['partitions']},"
                  f"{r['n_requests']},{r['stagger_ms']},{lat:.4f},"
                  f"{sp:.2f},{r['cache_hit_rate']:.2f},"
                  f"{r['mean_queue_depth']:.1f},{r['result_hash']},"
                  f"{r['identical_topk']}")
        write_bench_json(r, args.json, "engine_ab")
        assert r["engine_s"] < r["batch_loop_s"], \
            "engine must beat the per-batch loop on mean latency " \
            "under a staggered trace"
        return 0

    if args.fused:
        r = run_fused_ab(args.dataset or "opendata", args.partitions,
                         args.batch_size, k=args.k,
                         verifier=args.verifier, fused=fused)
        print("dataset,schedule,partitions,batch_size,"
              "mean_latency_per_query_s,speedup_vs_overlap,"
              "transfers,waves,device_rounds,result_hash,identical_topk")
        for name, lat, sp, tr in (
                ("fused", r["fused_s"], r["speedup"],
                 r["fused_transfers"]),
                ("overlap", r["overlap_s"], 1.0, r["overlap_transfers"])):
            print(f"{r['dataset']},{name},{r['partitions']},"
                  f"{r['batch_size']},{lat:.4f},{sp:.2f},{tr},"
                  f"{r['waves']},{r['device_rounds']},"
                  f"{r['result_hash']},{r['identical_topk']}")
        write_bench_json({
            "modes": {
                "fused": {"mean_latency_per_query_s": r["fused_s"],
                          "transfers": r["fused_transfers"]},
                "overlap": {"mean_latency_per_query_s": r["overlap_s"],
                            "transfers": r["overlap_transfers"]},
            },
            "speedup": r["speedup"], "result_hash": r["result_hash"],
            "dataset": r["dataset"], "partitions": r["partitions"],
            "batch_size": r["batch_size"], "verifier": r["verifier"],
        }, args.json, "fused_ab")
        assert r["fused_transfers"] < r["overlap_transfers"], \
            "fused wave must reduce host<->device transfers"
        return 0

    if args.overlap:
        r = run_partition_ab(args.dataset or "opendata", args.partitions,
                             args.batch_size, k=args.k,
                             verifier=args.verifier)
        print("dataset,schedule,partitions,batch_size,"
              "mean_latency_per_query_s,speedup_vs_sequential,"
              "bound_raises,backward_raises,identical_topk")
        for name, lat, sp in (("overlap", r["overlap_s"], r["speedup"]),
                              ("sequential", r["sequential_s"], 1.0)):
            print(f"{r['dataset']},{name},{r['partitions']},"
                  f"{r['batch_size']},{lat:.4f},{sp:.2f},"
                  f"{r['bound_raises']},{r['backward_raises']},"
                  f"{r['identical_topk']}")
        write_bench_json({
            "modes": {
                "overlap": {"mean_latency_per_query_s": r["overlap_s"]},
                "sequential": {
                    "mean_latency_per_query_s": r["sequential_s"]},
            },
            "speedup": r["speedup"], "result_hash": r["result_hash"],
            "dataset": r["dataset"], "partitions": r["partitions"],
            "batch_size": r["batch_size"], "verifier": r["verifier"],
        }, args.json, "partition_ab")
        return 0

    if args.batched or args.per_query:
        r = run_ab(args.dataset or "opendata", args.batch_size, k=args.k,
                   verifier=args.verifier)
        print("dataset,mode,batch_size,mean_latency_per_query_s,"
              "speedup_vs_per_query,identical_topk")
        rows = [("batched", r["batched_s"], r["speedup"]),
                ("per-query", r["per_query_s"], 1.0)]
        if args.per_query:
            rows.reverse()
        for mode_name, lat, sp in rows:
            print(f"{r['dataset']},{mode_name},{r['batch_size']},"
                  f"{lat:.4f},{sp:.2f},{r['identical_topk']}")
        write_bench_json({
            "modes": {
                "batched": {"mean_latency_per_query_s": r["batched_s"]},
                "per_query": {
                    "mean_latency_per_query_s": r["per_query_s"]},
            },
            "speedup": r["speedup"], "result_hash": r["result_hash"],
            "dataset": r["dataset"], "batch_size": r["batch_size"],
            "verifier": r["verifier"],
        }, args.json, "batched_ab")
        return 0

    table_kw = {"k": args.k}
    if args.dataset:
        table_kw["datasets"] = (args.dataset,)
    print("dataset,sim,koios_s,baseline_s,baseline+_s,speedup,"
          "em_koios,em_baseline,mem_mb")
    for r in run(**table_kw):
        print(f"{r['dataset']},{r['sim']},{r['koios_s']:.2f},"
              f"{r['baseline_s']:.2f},{r['baseline_plus_s']:.2f},"
              f"{r['speedup']:.1f},{r['em_koios']:.0f},"
              f"{r['em_baseline']:.0f},{r['mem_mb']:.1f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
