"""Bring-up check of the fused KOIOS serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # placed shards on four chips

One chip: Twitter at its full Table-I scale (27,204 sets, avg 22.6, max
151, vocab 72,910), 300-d embeddings (the paper's FastText width), and
the paper's §VIII defaults (k=10, alpha=0.8, 10 partitions), served
through ``RequestEngine(schedule="fused")`` over a ``ShardedCollection``
for the default verifier (``hungarian``) and for ``auction``, whose
bidding rounds run the compiled ``auction_topk2`` Pallas kernel.  Each
pass must resolve to the fused schedule, serve every request, hash
equal to a host-wave engine on the same requests, and agree with a
plain NumPy/SciPy reference that shares no code with the system.

``--chips 4`` runs only the placed path: the 10 shards round-robin on
four chips, the fused engine with the mesh all-reduce-max bound
exchange, compared with the reference and with an unplaced one-chip
fused engine in the same process, plus a check that every shard's
arrays live on its own device and that four devices hold them.

Everything runs in this one process; it starts no other.  The script
exits nonzero, without a result line, when JAX finds no TPU or any
phase fails.  Its last line is the device report::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Compile times it prints are set-up, not metrics; the persistent
compilation cache is at ``JAX_COMPILATION_CACHE_DIR`` when set, else at
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DATASET, SCALE, DIM, PARTITIONS = "twitter", 1.0, 300, 10
K, ALPHA, N_QUERIES = 10, 0.8, 8

# Score tolerance against the float64 reference.  A served score is a
# float32 sum of at most 151 matched similarities (max set size), each
# within a few float32 ulps (2**-24 relative, sims <= 1) of its float64
# value, so the sum is off by well under 151 * 4 * 2**-24 ~ 4e-5.  The
# auction verifier's score is its primal value, certified within
# |Q| * auction_eps of the optimum, which widens its tolerance by that.
SCORE_TOL = 1e-4


def score_tol(query, params) -> float:
    """The tolerance a served score of ``query`` is held to."""
    if params.verifier == "hungarian":
        return SCORE_TOL
    return SCORE_TOL + len(query) * params.auction_eps


# ------------------------------------------------------------- reference
def reference_scores(coll, emb: np.ndarray, query: np.ndarray,
                     alpha: float) -> dict:
    """Semantic overlap of ``query`` with every set that has at least one
    edge at or above ``alpha``: float64 cosine of the embedding rows,
    identical tokens at 1.0, edges below alpha dropped, then a maximum
    weight matching (``scipy.optimize.linear_sum_assignment``).  Sets with
    no such edge score 0 and are left out.  Returns {set id: score}."""
    from scipy.optimize import linear_sum_assignment

    e = np.asarray(emb, np.float64)
    e = e / np.maximum(np.linalg.norm(e, axis=1, keepdims=True), 1e-12)
    query = np.asarray(query, np.int64)
    sims = np.clip(e[query] @ e.T, 0.0, 1.0)               # (|Q|, vocab)
    sims[np.arange(len(query)), query] = 1.0
    sims = np.where(sims >= alpha, sims, 0.0)
    token_hit = sims.max(axis=0) > 0.0
    sizes = np.diff(coll.set_indptr)
    owner = np.repeat(np.arange(coll.num_sets), sizes)
    out = {}
    for sid in np.unique(owner[token_hit[coll.set_tokens]]):
        toks = coll.set_tokens[coll.set_indptr[sid]:coll.set_indptr[sid + 1]]
        w = sims[:, toks]
        r, c = linear_sum_assignment(w, maximize=True)
        out[int(sid)] = float(w[r, c].sum())
    return out


def reference_topk(scores: dict, k: int):
    """(ids, scores) of the k best reference scores, descending."""
    order = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return (np.asarray([i for i, _ in order], np.int64),
            np.asarray([s for _, s in order], np.float64))


def topk_mismatches(ids, scores, ref: dict, k: int,
                    tol: float = SCORE_TOL) -> list:
    """Why a served top-k disagrees with the reference, as messages (an
    empty list means it agrees).  Scores must match rank by rank and id
    by id within ``tol``; ids must match up to ties at the k-th score:
    every served id scores at least the k-th reference score, and every
    set scoring clearly above it is served."""
    ids = np.asarray(ids, np.int64)
    scores = np.asarray(scores, np.float64)
    ref_ids, ref_scores = reference_topk(ref, k)
    bad = []
    if len(ids) != len(ref_ids):
        return [f"served {len(ids)} ids, reference has {len(ref_ids)}"]
    if len(ids) == 0:
        return bad
    if np.abs(scores - ref_scores).max() > tol:
        bad.append(f"rank scores differ by "
                   f"{np.abs(scores - ref_scores).max():.3g}")
    kth = ref_scores[-1]
    for i, s in zip(ids, scores):
        r = ref.get(int(i))
        if r is None:
            bad.append(f"set {i} served but has no edge >= alpha")
        elif abs(r - s) > tol:
            bad.append(f"set {i}: served {s:.6f}, reference {r:.6f}")
        elif r < kth - tol:
            bad.append(f"set {i} (reference {r:.6f}) is below the k-th "
                       f"reference score {kth:.6f}")
    missing = set(int(i) for i, s in zip(ref_ids, ref_scores)
                  if s > kth + tol) - set(int(i) for i in ids)
    if missing:
        bad.append(f"sets {sorted(missing)} above the k-th score not served")
    return bad


# ---------------------------------------------------------------- phases
def serve(engine, queries):
    """Serve ``queries`` once; returns (response dicts, served hash,
    seconds).  Every response must be served."""
    from repro.launch.serve import response_dict, served_hash

    t0 = time.perf_counter()
    responses = [response_dict(r) for r in engine.serve(queries)]
    dt = time.perf_counter() - t0
    bad = [r["status"] for r in responses if r["status"] != "ok"]
    if bad or len(responses) != len(queries):
        raise RuntimeError(f"{len(responses)} responses for {len(queries)} "
                           f"requests, statuses {bad}")
    return responses, served_hash(responses), dt


def check_reference(responses, refs: list, queries, params) -> None:
    """Raise unless every served top-k agrees with its reference."""
    for qi, (r, ref, q) in enumerate(zip(responses, refs, queries)):
        bad = topk_mismatches(r["ids"], r["scores"], ref, params.k,
                              score_tol(q, params))
        if bad:
            raise RuntimeError(f"request {qi} disagrees with the reference: "
                               + "; ".join(bad[:5]))


def fused_vs_host(collection, sim, params, queries, refs: list,
                  log=print) -> str:
    """One verifier pass: the fused engine (must resolve to ``fused``)
    and a host-wave engine serve the same requests; their hashes must
    match each other and a repeat of the fused serve, and the fused
    top-k must agree with the reference.  Returns the served hash."""
    from repro.runtime.engine import RequestEngine

    fused = RequestEngine(None, sim, params, collection=collection,
                          schedule="fused")
    if fused.schedule != "fused":
        raise RuntimeError(f"fused request resolved to {fused.schedule!r}")
    responses, h_fused, cold = serve(fused, queries)
    _, h_again, warm = serve(fused, queries)
    host = RequestEngine(None, sim, params, collection=collection,
                         schedule="wave")
    _, h_host, host_s = serve(host, queries)
    log(f"[{params.verifier}] schedule={fused.schedule} "
        f"requests={len(responses)} first_serve_s={cold:.3f} "
        f"repeat_serve_s={warm:.3f} host_wave_first_serve_s={host_s:.3f} "
        f"(compiles included; set-up, not metrics)")
    log(f"[{params.verifier}] fused_hash={h_fused} host_wave_hash={h_host} "
        f"repeat_hash={h_again}")
    if not h_fused == h_host == h_again:
        raise RuntimeError("fused and host-wave engines served different "
                           "results")
    check_reference(responses, refs, queries, params)
    log(f"[{params.verifier}] reference agreement: {len(responses)}/"
        f"{len(responses)} top-{params.k} lists within "
        f"{max(score_tol(q, params) for q in queries):g}")
    return h_fused


def placed_vs_one_chip(coll, sim, params, queries, refs: list, devices,
                       log=print) -> str:
    """The four-chip phase: shards placed round-robin on ``devices`` with
    the mesh bound exchange, against an unplaced fused engine and the
    reference.  Returns the served hash."""
    from repro.launch.mesh import bound_exchange_mesh
    from repro.runtime.collection import ShardedCollection
    from repro.runtime.engine import RequestEngine
    from repro.runtime.sharding import bound_exchange_for

    mesh = bound_exchange_mesh(len(devices))
    placed = ShardedCollection.build(coll, PARTITIONS, devices=devices)
    eng = RequestEngine(None, sim, params, collection=placed,
                        schedule="fused",
                        bound_exchange=bound_exchange_for(mesh))
    if eng.schedule != "fused":
        raise RuntimeError(f"fused request resolved to {eng.schedule!r}")
    responses, h_placed, cold = serve(eng, queries)
    homes = set()
    for s in placed.shards:
        arrays = (*s.csr_arrays(), *s.wave_operands()[:2], s.table_for(sim))
        where = set().union(*(a.devices() for a in arrays))
        if where != {s.device}:
            raise RuntimeError(f"shard {s.sid} pinned to {s.device} has "
                               f"arrays on {where}")
        homes.add(s.device)
    if len(homes) != len(devices):
        raise RuntimeError(f"shards live on {len(homes)} devices, "
                           f"expected {len(devices)}")
    one = RequestEngine(None, sim, params,
                        collection=ShardedCollection.build(coll, PARTITIONS),
                        schedule="fused")
    _, h_one, _ = serve(one, queries)
    log(f"[placed] {placed.num_shards} shards on {len(homes)} devices "
        f"({sorted(d.id for d in homes)}), mesh={dict(mesh.shape)} "
        f"requests={len(responses)} first_serve_s={cold:.3f} "
        f"(compiles included; set-up, not a metric)")
    log(f"[placed] placed_hash={h_placed} one_chip_hash={h_one}")
    if h_placed != h_one:
        raise RuntimeError("placed shards served different results from "
                           "the one-chip engine")
    check_reference(responses, refs, queries, params)
    log(f"[placed] reference agreement: {len(responses)}/{len(responses)} "
        f"top-{params.k} lists within "
        f"{max(score_tol(q, params) for q in queries):g}")
    return h_placed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the placed-shard phase on four chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.core import SearchParams
    from repro.data import (EmbeddingTableProvider, dataset_preset,
                            make_embeddings, sample_queries)
    from repro.runtime.collection import ShardedCollection
    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}")

    t0 = time.perf_counter()
    coll = dataset_preset(DATASET, SCALE, seed=0)
    emb = make_embeddings(coll.vocab_size, dim=DIM, seed=0)
    sim = EmbeddingTableProvider(emb)
    queries = sample_queries(coll, N_QUERIES, seed=1)
    print(f"[corpus] {DATASET}@{SCALE}: {coll.num_sets} sets, avg "
          f"{coll.set_sizes.mean():.1f}, max {coll.set_sizes.max()}, vocab "
          f"{coll.vocab_size}, dim {DIM}; {len(queries)} queries "
          f"(sizes {[len(q) for q in queries]}); "
          f"built in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    refs = [reference_scores(coll, emb, q, ALPHA) for q in queries]
    print(f"[reference] float64 cosine + linear_sum_assignment over "
          f"{sum(len(r) for r in refs)} candidate sets in "
          f"{time.perf_counter() - t0:.1f}s")

    hungarian = SearchParams(k=K, alpha=ALPHA)
    if args.chips == 4:
        placed_vs_one_chip(coll, sim, hungarian, queries, refs,
                           devices[:4])
    else:
        collection = ShardedCollection.build(coll, PARTITIONS)
        for verifier in ("hungarian", "auction"):
            params = SearchParams(k=K, alpha=ALPHA, verifier=verifier)
            fused_vs_host(collection, sim, params, queries, refs)
    print(f"[done] wall {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
