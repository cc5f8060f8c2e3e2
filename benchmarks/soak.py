"""Fault-injected soak harness for the serving plane (DESIGN.md §6).

Two legs, both over ONE shared :class:`ShardedCollection` resource:

* ``fault_soak`` — a sustained Zipf-skewed request trace (a small hot
  query pool drawn with Zipf weights, staggered arrivals) replayed
  through an :class:`AdmissionRouter` fleet while a seeded
  :class:`FaultPlan` crashes one replica mid-trace, injects a transient
  verifier error on another, and stalls a third.  The harness asserts
  the recovery contract end-to-end: the trace completes, no request is
  lost or duplicated, and every SERVED response (``ok`` or ``retried``)
  is bit-identical to the fault-free one-shot ``search_batch`` over the
  same collection.  Reported: p50/p99 admit->respond latency, shed
  rate, retry count, quarantine/revive counts, and recovery time
  (first quarantine -> first post-failover serve).

* ``overload`` — the same trace with deadlines tight enough that a
  slice of the requests is doomed at admission, served with
  ``shed_deadlines=True``.  Shed responses must carry ``status='shed'``
  with ZERO waves (the ``engine:shed`` instrument events are the audit
  trail that no wave tile was spent on them), while the surviving
  requests stay bit-identical.

* ``live_update`` — the crash-consistency leg (DESIGN.md §6.5): the
  first half of the trace is admitted, a replica crashes, and a live
  ``commit()`` (remove the hot top-1 set + add two) lands mid-flight
  with a snapshot on commit; the second half is admitted post-commit.
  Asserted: exactly-once rids; every served response bit-identical to
  the one-shot reference of ITS epoch (pre-commit admissions pinned to
  the old snapshot, post-commit ones reflecting the new sets);
  post-commit responses all on the new epoch; and a restore from the
  snapshot serving bit-identically to the committed head.

All legs merge their records into ``BENCH_soak.json`` (CI uploads it;
the trajectory stays comparable across PRs).

Usage:
    PYTHONPATH=src python -m benchmarks.soak [--fast] [--replicas 4]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import tempfile
import time

import numpy as np

from repro.core import KoiosSearch, SearchParams
from repro.data import sample_queries
from repro.runtime import instrument
from repro.runtime.collection import ShardedCollection
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.engine import AdmissionRouter, RouterPolicy
from repro.runtime.fault import FaultEvent, FaultPlan

from .common import world


def result_hash(results) -> str:
    """Stable digest of a list of SearchResults (ids + score bits)."""
    h = hashlib.sha256()
    for r in results:
        h.update(np.ascontiguousarray(r.ids).tobytes())
        h.update(np.ascontiguousarray(r.lb).tobytes())
    return h.hexdigest()[:16]


def zipf_trace(coll, n_requests: int, pool: int = 12, zipf_a: float = 1.3,
               seed: int = 5):
    """A skewed serving trace: ``pool`` unique queries, request i drawing
    query rank r with probability ~ 1/r^a (the stream-cache-friendly
    skew real set-search traffic shows)."""
    uniq = sample_queries(coll, pool, seed=seed)
    ranks = np.arange(1, pool + 1, dtype=np.float64)
    w = ranks ** -zipf_a
    rng = np.random.default_rng(seed)
    picks = rng.choice(pool, size=n_requests, p=w / w.sum())
    return [uniq[i] for i in picks], picks


def _mid_trace_plan(crash_replica: int = 1, crash_step: int = 2
                    ) -> FaultPlan:
    """The soak's pinned schedule: one permanent crash mid-trace, one
    revivable transient verifier error, one sub-timeout stall.  Pinned
    (not ``FaultPlan.random``) so the BENCH artifact is comparable
    across runs; the seeded generator is exercised by tests."""
    return FaultPlan([
        FaultEvent("crash", replica=crash_replica, step=crash_step),
        FaultEvent("verify_error", replica=2, step=1),
        FaultEvent("stall", replica=3, step=1, stall_s=0.005),
    ])


def run_fault_soak(dataset="opendata", replicas=4, partitions=2,
                   n_requests=48, pool=12, zipf_a=1.3, k=10, alpha=0.8,
                   stagger_ms=2.0, seed=5):
    """The failover leg: Zipf trace + mid-trace faults; asserts
    completion, exactly-once responses, and served bit-parity."""
    assert replicas >= 4, "the pinned fault plan addresses replicas 1..3"
    params = SearchParams(k=k, alpha=alpha)
    coll, sim = world(dataset)
    sc = ShardedCollection.build(coll, partitions)
    queries, picks = zipf_trace(coll, n_requests, pool=pool,
                                zipf_a=zipf_a, seed=seed)

    # fault-free one-shot reference over the SAME collection resource
    ref = KoiosSearch(None, sim, params,
                      collection=sc).search_batch(queries)

    plan = _mid_trace_plan()
    router = AdmissionRouter(None, sim, params, replicas=replicas,
                             collection=sc, policy=RouterPolicy())
    router.warmup(queries[:2])
    for eng in router.engines:      # attach faults AFTER warmup so the
        eng.fault_plan = plan       # step addresses count live traffic
        eng._step_no = 0

    t0 = time.monotonic()
    now = router.clock()
    gap = stagger_ms / 1e3
    with instrument.counting() as events:
        for i, q in enumerate(queries):
            router.submit(q, arrival=now + i * gap)
        responses = sorted(router.drain(), key=lambda r: r.rid)
    wall_s = time.monotonic() - t0

    # ---- the recovery contract ----
    rids = [r.rid for r in responses]
    assert rids == list(range(n_requests)), \
        f"lost/duplicated requests: {len(rids)} responses"   # exactly once
    served = [r for r in responses if r.served]
    for r in served:                       # bit-identical to fault-free
        assert result_hash([r.result]) == result_hash([ref[r.rid]]), \
            f"request {r.rid} diverged after {r.retries} retries"
    retried = [r for r in served if r.status == "retried"]
    assert plan.take(1, 2) == [] and any(
        e.kind == "crash" for e in plan.fired), "crash never fired"
    assert retried, "the crash evacuated no requests (trace too short?)"

    s = router.summary()
    q_times = [q["t"] for q in router.quarantine_log
               if q["reason"] != "revived"]
    recovery_s = (router._t_last_recovered - min(q_times)
                  if q_times and router._t_last_recovered else None)
    lats = sorted(r.latency_s for r in served)
    qtile = lambda q: lats[min(len(lats) - 1,          # noqa: E731
                               int(q * len(lats)))] if lats else 0.0
    return {
        "dataset": dataset, "replicas": replicas, "partitions": partitions,
        "requests": n_requests, "query_pool": pool, "zipf_a": zipf_a,
        "stagger_ms": stagger_ms,
        "unique_hot_share": float(np.mean(picks == picks.min())),
        "faults_fired": [e.kind for e in plan.fired],
        "served": len(served), "retried": len(retried),
        "retries": s["retries"], "shed": s["shed"], "failed": s["failed"],
        "shed_rate": s["shed"] / n_requests,
        "quarantines": s["quarantines"],
        "revives": sum(q["reason"] == "revived"
                       for q in router.quarantine_log),
        "recovery_s": recovery_s,
        "p50_latency_s": qtile(0.50), "p99_latency_s": qtile(0.99),
        "router_events": {k: v for k, v in events.items()
                          if k.startswith("router:")},
        "served_hash": result_hash([r.result for r in served]),
        "reference_hash": result_hash([ref[r.rid] for r in served]),
        "wall_s": wall_s,
    }


def run_overload(dataset="opendata", replicas=2, partitions=2,
                 n_requests=24, pool=8, zipf_a=1.3, k=10, alpha=0.8,
                 doom_every=3, seed=6):
    """The shedding leg: every ``doom_every``-th request carries an
    already-expired deadline; with ``shed_deadlines=True`` those respond
    ``status='shed'`` BEFORE any wave tile is spent (waves == 0, one
    ``engine:shed`` event each) and the rest stay bit-identical."""
    params = SearchParams(k=k, alpha=alpha)
    coll, sim = world(dataset)
    sc = ShardedCollection.build(coll, partitions)
    queries, _ = zipf_trace(coll, n_requests, pool=pool,
                            zipf_a=zipf_a, seed=seed)
    ref = KoiosSearch(None, sim, params,
                      collection=sc).search_batch(queries)

    router = AdmissionRouter(None, sim, params, replicas=replicas,
                             collection=sc, shed_deadlines=True)
    router.warmup(queries[:2])
    t0 = time.monotonic()
    now = router.clock()
    doomed = [i % doom_every == doom_every - 1 for i in range(n_requests)]
    with instrument.counting() as events:
        deadlines = [now - 1e-3 if d else None for d in doomed]
        responses = router.serve(queries, deadlines=deadlines)
    wall_s = time.monotonic() - t0

    assert [r.rid for r in responses] == list(range(n_requests))
    shed = [r for r in responses if r.status == "shed"]
    assert [r.rid for r in shed] == [i for i, d in enumerate(doomed) if d]
    assert all(r.waves == 0 for r in shed), \
        "a shed request occupied a wave tile"    # shed BEFORE dispatch
    assert events["engine:shed"] == len(shed)    # the instrument proof
    ok = [r for r in responses if r.status == "ok"]
    assert len(ok) + len(shed) == n_requests
    for r in ok:
        assert result_hash([r.result]) == result_hash([ref[r.rid]])

    lats = sorted(r.latency_s for r in ok)
    qtile = lambda q: lats[min(len(lats) - 1,          # noqa: E731
                               int(q * len(lats)))] if lats else 0.0
    return {
        "dataset": dataset, "replicas": replicas, "partitions": partitions,
        "requests": n_requests, "doom_every": doom_every,
        "shed": len(shed), "shed_rate": len(shed) / n_requests,
        "shed_events": int(events["engine:shed"]),
        "shed_waves_total": sum(r.waves for r in shed),
        "p50_latency_s": qtile(0.50), "p99_latency_s": qtile(0.99),
        "served_hash": result_hash([r.result for r in ok]),
        "wall_s": wall_s,
    }


def run_live_update(dataset="opendata", replicas=4, partitions=2,
                    n_requests=32, pool=10, zipf_a=1.3, k=10, alpha=0.8,
                    stagger_ms=2.0, seed=7, snapshot_dir=None):
    """The crash-consistency leg (DESIGN.md §6.5): admit half the trace,
    crash a replica, land a live ``commit()`` mid-flight (snapshotting on
    commit), admit the rest, then assert the epoch contract: exactly-once
    rids; every served response bit-identical to the one-shot reference
    of ITS epoch; post-commit admissions all on the new epoch; and a
    restore from the snapshot serving bit-identically to the live head."""
    assert replicas >= 2 and n_requests >= 8
    params = SearchParams(k=k, alpha=alpha)
    coll, sim = world(dataset)
    sc = ShardedCollection.build(coll, partitions)
    queries, picks = zipf_trace(coll, n_requests, pool=pool,
                                zipf_a=zipf_a, seed=seed)
    half = n_requests // 2

    # epoch-0 one-shot reference over the whole trace
    ref_old = KoiosSearch(None, sim, params,
                          collection=sc).search_batch(queries)

    # the update removes the top-1 set of the hottest POST-commit query,
    # so the new epoch's results provably differ from the old snapshot's
    hot_pick = int(np.bincount(picks[half:]).argmax())
    hot_rid = half + int(np.argmax(picks[half:] == hot_pick))
    victim = int(ref_old[hot_rid].ids[0])

    router = AdmissionRouter(None, sim, params, replicas=replicas,
                             collection=sc, policy=RouterPolicy())
    router.warmup(queries[:2])
    plan = FaultPlan([FaultEvent("crash", replica=1, step=2)])
    for eng in router.engines:      # one mid-trace replica kill rides
        eng.fault_plan = plan       # along with the live commit
        eng._step_no = 0

    tmpdir = snapshot_dir or tempfile.mkdtemp(prefix="koios_soak_snap_")
    sc.save(tmpdir)                             # epoch-0 baseline
    sc.on_commit(lambda s: s.save(tmpdir))      # snapshot on every commit

    t0 = time.monotonic()
    gap = stagger_ms / 1e3
    with instrument.counting() as events:
        now = router.clock()
        for i, q in enumerate(queries[:half]):
            router.submit(q, arrival=now + i * gap)
        pre = []                    # step until work is in flight/served
        while not pre:              # so the commit truly lands mid-trace
            pre.extend(router.step())

        upd = sc.begin_update()
        upd.remove_sets([victim])
        upd.add_sets([coll.get_set(1).copy(), coll.get_set(3).copy()])
        new_epoch = upd.commit()

        now = router.clock()
        for i, q in enumerate(queries[half:]):
            router.submit(q, arrival=now + i * gap)
        responses = sorted(pre + router.drain(), key=lambda r: r.rid)
    wall_s = time.monotonic() - t0

    # ---- the epoch contract ----
    rids = [r.rid for r in responses]
    assert rids == list(range(n_requests)), \
        f"lost/duplicated requests: {len(rids)} responses"   # exactly once
    assert new_epoch > 0 and sc.epoch == new_epoch

    # post-commit one-shot reference (head epoch)
    ref_new = KoiosSearch(None, sim, params,
                          collection=sc).search_batch(queries)
    served = [r for r in responses if r.served]
    for r in served:        # bit-identical to the reference of ITS epoch
        ref = ref_old if r.epoch == 0 else ref_new
        assert result_hash([r.result]) == result_hash([ref[r.rid]]), \
            f"request {r.rid} (epoch {r.epoch}) diverged"
    post = [r for r in served if r.rid >= half]
    assert post and all(r.epoch == new_epoch for r in post), \
        "a post-commit admission served against a stale epoch"
    assert not np.array_equal(ref_old[hot_rid].ids, ref_new[hot_rid].ids), \
        "the commit changed nothing the post-commit trace can observe"
    assert any(e.kind == "crash" for e in plan.fired), "crash never fired"

    # restore from the snapshot left by the commit hook: same epoch,
    # bit-identical one-shot serving vs the live committed head
    restored = ShardedCollection.restore(tmpdir)
    assert restored is not None and restored.epoch == new_epoch
    ref_restored = KoiosSearch(None, sim, params,
                               collection=restored).search_batch(queries)
    assert (result_hash(ref_restored) == result_hash(ref_new)), \
        "restore-from-snapshot diverged from the committed head"

    s = router.summary()
    lats = sorted(r.latency_s for r in served)
    qtile = lambda q: lats[min(len(lats) - 1,          # noqa: E731
                               int(q * len(lats)))] if lats else 0.0
    pre_served = [r for r in served if r.epoch == 0]
    post_served = [r for r in served if r.epoch != 0]
    return {
        "dataset": dataset, "replicas": replicas, "partitions": partitions,
        "requests": n_requests, "query_pool": pool, "zipf_a": zipf_a,
        "epoch": int(sc.epoch), "removed_set": victim, "added_sets": 2,
        "commit_shared_shards": sc._last_commit["shards_shared"],
        "commit_rebuilt_shards": sc._last_commit["shards_rebuilt"],
        "served": len(served),
        "served_old_epoch": len(pre_served),
        "served_new_epoch": len(post_served),
        "retries": s["retries"], "shed": s["shed"], "failed": s["failed"],
        "quarantines": s["quarantines"],
        "resyncs": int(events.get("engine:resync", 0)),
        "rollouts": int(events.get("router:rollout", 0)),
        "commits": int(events.get("collection:commit", 0)),
        "p50_latency_s": qtile(0.50), "p99_latency_s": qtile(0.99),
        "served_hash": result_hash([r.result for r in served]),
        "reference_hash": result_hash(
            [(ref_old if r.epoch == 0 else ref_new)[r.rid] for r in served]),
        "restored_hash_matches": True,
        "snapshot_dir": tmpdir,
        "wall_s": wall_s,
    }


def write_bench_json(record: dict, path: str, mode: str) -> None:
    """BENCH_soak.json — each leg's record merged under
    ``records[mode]``, so every leg's trajectory stays comparable across
    PRs."""
    if not path:
        return
    doc = {"benchmark": "soak", "records": {}}
    try:
        with open(path) as f:
            prev = json.load(f)
        if "records" in prev:
            doc["records"] = prev["records"]
    except (OSError, ValueError):
        pass
    doc["records"][mode] = record
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"[bench] wrote {path} (mode={mode}, "
          f"{len(doc['records'])} records)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="opendata")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--partitions", type=int, default=2)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--fast", action="store_true",
                    help="trim the trace for CI smoke (~20s)")
    ap.add_argument("--json", default="BENCH_soak.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    n = 24 if args.fast else args.requests

    print("leg,requests,p50_s,p99_s,shed_rate,retries,quarantines,"
          "recovery_s,bit_identical")
    r = run_fault_soak(args.dataset, replicas=args.replicas,
                       partitions=args.partitions, n_requests=n)
    ok = r["served_hash"] == r["reference_hash"]
    rec = f"{r['recovery_s']:.4f}" if r["recovery_s"] is not None else "-"
    print(f"fault_soak,{r['requests']},{r['p50_latency_s']:.4f},"
          f"{r['p99_latency_s']:.4f},{r['shed_rate']:.2f},{r['retries']},"
          f"{r['quarantines']},{rec},{ok}")
    write_bench_json(r, args.json, "fault_soak")

    o = run_overload(args.dataset, partitions=args.partitions,
                     n_requests=max(n // 2, 12))
    print(f"overload,{o['requests']},{o['p50_latency_s']:.4f},"
          f"{o['p99_latency_s']:.4f},{o['shed_rate']:.2f},0,0,-,True")
    write_bench_json(o, args.json, "overload")

    u = run_live_update(args.dataset, replicas=args.replicas,
                        partitions=args.partitions,
                        n_requests=max(2 * (n // 3), 16))
    ok = u["served_hash"] == u["reference_hash"]
    print(f"live_update,{u['requests']},{u['p50_latency_s']:.4f},"
          f"{u['p99_latency_s']:.4f},0.00,{u['retries']},"
          f"{u['quarantines']},-,{ok}")
    print(f"[live_update] epoch={u['epoch']} "
          f"shards shared={u['commit_shared_shards']} "
          f"rebuilt={u['commit_rebuilt_shards']} "
          f"served old/new={u['served_old_epoch']}/{u['served_new_epoch']} "
          f"resyncs={u['resyncs']} restored_ok={u['restored_hash_matches']}")
    write_bench_json(u, args.json, "live_update")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
