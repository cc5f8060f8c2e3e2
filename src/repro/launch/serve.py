"""Serving driver: a thin shell over the continuous-batching request
engine (``repro.runtime.engine``, DESIGN.md §3.2).

Every request is admitted into the engine's queue (optional deadlines),
coalesced into the next partition wave with whatever else has arrived
(mid-flight joins are sound — row numerics are schedule-invariant),
served through the LRU token-stream cache and pow2 shape buckets, and
responded to with its TRUE admit->respond latency — the historical
``serve_batch`` reported one amortized number for every query in the
batch.  Each wave's partition groups run as fused on-device programs
(DESIGN.md §3.1) on a TPU backend; elsewhere the engine serves host
waves, unless ``--interpret`` runs the fused programs with Pallas in
interpret mode.  ``--mesh-bounds`` runs the theta_lb exchange as a real
all-reduce-max over the repository mesh (DESIGN.md §5).  ``--per-query``
keeps the per-query one-shot loop as the A/B baseline (bit-identical
results).  ``--deadline-ms``/``--shed`` exercise
the fault-tolerant serving plane (DESIGN.md §6): per-request deadlines
with deadline-aware shedding, and the summary reports p50/p99 latency,
deadline-met ratio, and shed/retry/failed accounting.

Crash consistency (DESIGN.md §6.5): ``--snapshot-dir`` restores the
collection from the latest epoch manifest on startup (falling back to a
fresh build, snapshotted immediately) and re-snapshots on every live-
update commit; ``--update-after N`` applies a deterministic live update
(remove set 0, add two copied sets) once N requests have been served;
``--kill-after-update`` exits with code 17 right after the commit+
snapshot (the CI restart-recovery job's crash point); ``--skip N``
resumes the request trace at global request N after a restart.  The
``served_hash`` printed at the end is the restart-parity check: a run
killed after the update and a restored run serving the remaining trace
hash to exactly the uninterrupted run's pre/post-update hashes.

Smoke scale:
    PYTHONPATH=src python -m repro.launch.serve --requests 4 --k 5
"""
from __future__ import annotations

import argparse
import hashlib
import time

import numpy as np

from ..core import (EmbeddingSimilarity, KoiosSearch, SearchParams)
from ..data import (EmbeddingTableProvider, dataset_preset, make_embeddings,
                    sample_queries)
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.engine import RequestEngine


def response_dict(r) -> dict:
    """One EngineResponse -> the serving-API response payload."""
    return {
        "ids": r.result.ids.tolist(),
        "scores": r.result.lb.tolist(),
        "status": r.status,                     # ok | shed | retried | failed
        "retries": r.retries,
        "reason": r.reason,
        "latency_s": round(r.latency_s, 4),     # true per-request
        "queue_s": round(r.queue_s, 4),
        "waves": r.waves,
        "stream_cache_hit": r.stream_hit,
        "deadline_met": r.deadline_met,
        "stats": r.result.stats.as_dict(),
    }


def served_hash(results) -> str:
    """Order-sensitive digest of the SERVED responses (ids + scores) —
    the restart-recovery parity check: equal hashes mean bit-identical
    served results, whatever process lifetimes produced them."""
    h = hashlib.sha256()
    for r in results:
        if r.get("status", "ok") in ("ok", "retried"):
            h.update(np.asarray(r["ids"], np.int64).tobytes())
            h.update(np.asarray(r["scores"], np.float64).tobytes())
    return h.hexdigest()[:16]


def _demo_update(collection, base_coll) -> int:
    """The deterministic live update of ``--update-after``: remove set 0,
    add copies of base sets 1 and 2.  Pure function of the BASE corpus,
    so an interrupted run and its restored successor commit the same
    epoch-1 repository bit-for-bit."""
    u = collection.begin_update()
    u.remove_sets([0])
    u.add_sets([base_coll.get_set(1).copy(), base_coll.get_set(2).copy()])
    return u.commit()


class SearchServer:
    """Request-engine serving with a one-shot per-query baseline.

    ``serve_batch`` admits the batch into the :class:`RequestEngine`
    and drains it: every response carries its own admit->respond
    latency, queue time, wave count, and stream-cache attribution.
    ``batched=False`` falls back to the per-query one-shot loop
    (identical results).  Both follow ``params.fused``: fused device
    waves on a TPU or with ``'interpret'``, host waves otherwise.

    The repository lives in ONE :class:`ShardedCollection` resource
    (built here, optionally placed across ``shards`` devices) shared by
    the one-shot baseline and every engine replica — one front door over
    one logical collection (DESIGN.md §5).  ``replicas > 1`` serves
    through an :class:`~repro.runtime.engine.AdmissionRouter` fleet."""

    def __init__(self, coll, sim, params: SearchParams, partitions: int,
                 bound_exchange=None,
                 stream_cache_bytes: int = 64 << 20, replicas: int = 1,
                 shards: int = 0, place: bool = False,
                 shed_deadlines: bool = False, fault_plan=None,
                 collection=None):
        from ..runtime.collection import ShardedCollection
        from ..runtime.engine import AdmissionRouter

        # collection= injects a pre-existing resource — the restart path
        # restores one from a --snapshot-dir manifest instead of building
        if collection is None:
            collection = ShardedCollection.build(
                coll, shards or partitions,
                devices="auto" if place else None)
        self.collection = collection
        self.one_shot = KoiosSearch(None, sim, params,
                                    bound_exchange=bound_exchange,
                                    collection=self.collection)
        engine_kwargs = dict(
            schedule="fused",
            bound_exchange=bound_exchange,
            stream_cache_bytes=stream_cache_bytes,
            shed_deadlines=shed_deadlines)
        if fault_plan is not None and replicas > 1:
            engine_kwargs["fault_plan"] = fault_plan
        if replicas > 1:
            self.engine = AdmissionRouter(
                None, sim, params, replicas=replicas,
                collection=self.collection, **engine_kwargs)
        else:
            self.engine = RequestEngine(
                None, sim, params, collection=self.collection,
                **engine_kwargs)

    def serve_batch(self, queries, batched: bool = True, deadlines=None):
        """One request batch -> list of response dicts (request order)."""
        queries = [np.asarray(q, np.int32) for q in queries]
        if batched:
            responses = self.engine.serve(queries, deadlines=deadlines)
            return [response_dict(r) for r in responses]
        out = []
        for q in queries:
            t0 = time.monotonic()
            res = self.one_shot.search(q)
            out.append({
                "ids": res.ids.tolist(),
                "scores": res.lb.tolist(),
                "latency_s": round(time.monotonic() - t0, 4),
                "stats": res.stats.as_dict(),
            })
        return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="opendata")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--alpha", type=float, default=0.8)
    ap.add_argument("--partitions", type=int, default=2)
    ap.add_argument("--shards", type=int, default=0,
                    help="shard count of the collection resource "
                         "(defaults to --partitions; the shards ARE the "
                         "scheduler's partitions)")
    ap.add_argument("--place", action="store_true",
                    help="pin shard i's device arrays to device i "
                         "(round-robin over jax.devices()); waves run "
                         "where their shard lives and the theta_lb "
                         "carry hops between shard devices")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas over the ONE shared collection "
                         "resource, behind the admission router "
                         "(load-routed, globally ordered responses)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--stagger-ms", type=float, default=0.0,
                    help="replay the request trace with this inter-arrival "
                         "gap instead of submitting each batch at once "
                         "(continuous batching joins mid-flight)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (admit + this many ms); "
                         "reported met/missed per response, and with "
                         "--shed doomed requests are dropped before "
                         "occupying a wave tile (status=shed)")
    ap.add_argument("--shed", action="store_true",
                    help="deadline-aware shedding (DESIGN.md §6): requests "
                         "whose deadline is already unreachable respond "
                         "status=shed instead of burning wave tiles")
    ap.add_argument("--per-query", action="store_true",
                    help="serve each query independently through the "
                         "one-shot path (A/B baseline for the engine)")
    sched = ap.add_mutually_exclusive_group()
    sched.add_argument("--sequential", action="store_true",
                       help="serve host waves (SearchParams(fused='off'), "
                            "--per-query included) instead of the fused "
                            "device waves; bit-identical results")
    sched.add_argument("--interpret", action="store_true",
                       help="run the fused wave programs off the chip, "
                            "Pallas kernels in interpret mode (without it "
                            "a non-TPU backend serves host waves)")
    ap.add_argument("--mesh-bounds", action="store_true",
                    help="run the theta_lb exchange as an all-reduce-max "
                         "over a device mesh (DESIGN.md §5)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="crash consistency (DESIGN.md §6.5): restore the "
                         "collection from this directory's epoch manifest "
                         "on startup (build fresh + snapshot when none "
                         "exists) and re-snapshot on every live-update "
                         "commit")
    ap.add_argument("--update-after", type=int, default=0,
                    help="apply the deterministic demo live update "
                         "(remove set 0, add two copied sets) once this "
                         "many requests have been served; 0 = never")
    ap.add_argument("--kill-after-update", action="store_true",
                    help="exit with code 17 immediately after the "
                         "--update-after commit (and its snapshot) — the "
                         "restart-recovery smoke's crash point")
    ap.add_argument("--skip", type=int, default=0,
                    help="skip the first N requests of the trace, keeping "
                         "global request numbering (restart resume)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    bound_exchange = None
    if args.mesh_bounds:
        from ..runtime.sharding import bound_exchange_for
        from .mesh import bound_exchange_mesh
        bound_exchange = bound_exchange_for(bound_exchange_mesh())

    print(f"[serve] building corpus ({args.dataset} @ {args.scale})")
    coll = dataset_preset(args.dataset, scale=args.scale, seed=0)
    emb = make_embeddings(coll.vocab_size, dim=args.dim, seed=0)
    sim = EmbeddingTableProvider(emb)
    fused = ("off" if args.sequential else
             "interpret" if args.interpret else "auto")
    params = SearchParams(k=args.k, alpha=args.alpha, fused=fused)
    collection = None
    if args.snapshot_dir:
        from ..runtime.collection import ShardedCollection
        collection = ShardedCollection.restore(
            args.snapshot_dir, devices="auto" if args.place else None)
        if collection is not None:
            print(f"[serve] restored collection epoch "
                  f"{collection.epoch} from {args.snapshot_dir}")
    server = SearchServer(coll, sim, params, args.partitions,
                          bound_exchange=bound_exchange,
                          replicas=args.replicas, shards=args.shards,
                          place=args.place, shed_deadlines=args.shed,
                          collection=collection)
    if args.snapshot_dir:
        if collection is None:
            # nothing to restore: persist the initial epoch NOW, so a
            # crash before the first commit still restores epoch 0
            server.collection.save(args.snapshot_dir)
        server.collection.on_commit(
            lambda sc: sc.save(args.snapshot_dir))
    desc = server.collection.describe()
    placed = [s["device"] for s in desc["shards"] if s["device"]]
    print(f"[serve] corpus: {coll.num_sets} sets, vocab {coll.vocab_size}, "
          f"{server.collection.num_shards} shards"
          + (f" on {len(set(placed))} devices" if placed else "")
          + (f", {args.replicas} replicas" if args.replicas > 1 else ""))

    # queries ALWAYS sample from the pristine built corpus — never the
    # restored collection — so an interrupted run and its restored
    # successor replay the identical request trace (restart parity)
    queries = sample_queries(coll, args.requests, seed=1)
    dl = args.deadline_ms / 1e3 if args.deadline_ms else None
    served_pre: list = []           # responses before the live update
    served_post: list = []          # responses at/after it
    updated = server.collection.epoch > 0      # restored past the update
    for lo in range(args.skip, len(queries), args.batch_size):
        batch = queries[lo:lo + args.batch_size]
        if args.stagger_ms and not args.per_query:
            now = server.engine.clock()
            for i, q in enumerate(batch):
                t_arr = now + i * args.stagger_ms / 1e3
                server.engine.submit(
                    q, arrival=t_arr,
                    deadline=t_arr + dl if dl else None)
            results = [response_dict(r)
                       for r in sorted(server.engine.drain(),
                                       key=lambda r: r.rid)]
        else:
            now = server.engine.clock()
            results = server.serve_batch(
                batch, batched=not args.per_query,
                deadlines=[now + dl] * len(batch) if dl else None)
        (served_post if updated else served_pre).extend(results)
        for i, r in enumerate(results):
            if not args.per_query and r["status"] in ("shed", "failed"):
                print(f"req {lo+i}: {r['status']} ({r['reason']}) "
                      f"lat={r['latency_s']}s waves={r['waves']}")
                continue
            extra = ("" if args.per_query else
                     f"status={r['status']} queue={r['queue_s']}s "
                     f"waves={r['waves']} "
                     f"cached={r['stream_cache_hit']} ")
            print(f"req {lo+i}: top-{args.k} ids={r['ids'][:5]}... "
                  f"scores={[round(s,2) for s in r['scores'][:5]]} "
                  f"lat={r['latency_s']}s {extra}"
                  f"verified={r['stats']['exact_matches']}")
        if (args.update_after and not updated
                and lo + len(batch) - args.skip >= args.update_after):
            epoch = _demo_update(server.collection, coll)
            updated = True
            print(f"[serve] live update committed: epoch {epoch} "
                  f"({server.collection.coll.num_sets} sets)"
                  + (f", snapshotted to {args.snapshot_dir}"
                     if args.snapshot_dir else ""))
            if args.kill_after_update:
                print(f"[serve] served_hash={served_hash(served_pre)} "
                      f"requests={len(served_pre)} epoch=0")
                print("[serve] killed after update (exit 17)")
                return 17
    if not args.per_query:
        if served_pre:
            print(f"[serve] pre_update_hash={served_hash(served_pre)} "
                  f"requests={len(served_pre)}")
        if served_post:
            print(f"[serve] post_update_hash={served_hash(served_post)} "
                  f"requests={len(served_post)}")
        print(f"[serve] served_hash="
              f"{served_hash(served_pre + served_post)} "
              f"requests={len(served_pre) + len(served_post)} "
              f"epoch={server.collection.epoch}")
    if not args.per_query:
        s = server.engine.summary()
        replicas = s.get("per_replica", [s])
        if "per_replica" in s:
            print(f"  [router] replicas={s['replicas']} "
                  f"(healthy={s['healthy_replicas']}) "
                  f"requests={s['requests']} waves={s['waves']} "
                  f"shed={s['shed']} retries={s['retries']} "
                  f"failed={s['failed']} "
                  f"quarantines={s['quarantines']} "
                  f"p50={s['p50_latency_s']:.4f}s "
                  f"p99={s['p99_latency_s']:.4f}s "
                  f"device_bytes={s['collection']['device_bytes']}")
        for ri, p in enumerate(replicas):
            cache = p["stream_cache"]
            tag = f"replica {ri}" if "per_replica" in s else "engine"
            print(f"  [{tag}] schedule={p['schedule']} "
                  f"requests={p['requests']} served={p['served']} "
                  f"shed={p['shed']} steps={p['steps']} "
                  f"mean_lat={p['mean_latency_s']:.4f}s "
                  f"p50={p['p50_latency_s']:.4f}s "
                  f"p95={p['p95_latency_s']:.4f}s "
                  f"p99={p['p99_latency_s']:.4f}s "
                  f"deadline_met={p['deadline_met_ratio']:.2f} "
                  f"mean_queue_depth={p['mean_queue_depth']:.1f} "
                  f"waves={p['scheduler']['waves']} "
                  f"cache_hit_rate={cache['hit_rate']:.2f} "
                  f"(hits={cache['hits']} misses={cache['misses']} "
                  f"evictions={cache['evictions']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
