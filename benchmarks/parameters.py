"""Paper Fig. 7: parameter analysis — response time vs (a) partitions,
(b) element-similarity threshold alpha, (c) result size k; (d) memory vs
alpha."""
from __future__ import annotations

import numpy as np

from repro.core import KoiosSearch, SearchParams
from repro.data import sample_queries
from repro.runtime.compile_cache import enable_compile_cache

from .common import memory_footprint_bytes, timed, world


def run(dataset="opendata", n_queries=2,
        partitions=(1, 2, 4), alphas=(0.7, 0.8, 0.9), ks=(1, 10, 50)):
    coll, sim = world(dataset)
    queries = sample_queries(coll, n_queries, seed=17)
    out = {"partitions": [], "alpha": [], "k": []}

    for p in partitions:
        engine = KoiosSearch(coll, sim, SearchParams(k=10, alpha=0.8),
                             partitions=p)
        t = sum(timed(engine.search, q)[1] for q in queries) / len(queries)
        out["partitions"].append({"partitions": p, "time_s": t})

    for a in alphas:
        engine = KoiosSearch(coll, sim, SearchParams(k=10, alpha=a))
        t = 0.0
        em = 0
        for q in queries:
            r, dt = timed(engine.search, q)
            t += dt
            em += r.stats.exact_matches
        out["alpha"].append({
            "alpha": a, "time_s": t / len(queries),
            "em": em / len(queries),
            "mem_mb": memory_footprint_bytes(
                dataset, int(np.mean([len(q) for q in queries])))["total"]
            / 1e6})

    for k in ks:
        engine = KoiosSearch(coll, sim, SearchParams(k=k, alpha=0.8))
        t = sum(timed(engine.search, q)[1] for q in queries) / len(queries)
        out["k"].append({"k": k, "time_s": t})
    return out


def main():
    enable_compile_cache()
    res = run()
    for key, rows in res.items():
        for r in rows:
            vals = ",".join(f"{k}={v:.3f}" if isinstance(v, float)
                            else f"{k}={v}" for k, v in r.items())
            print(f"param_{key}: {vals}")


if __name__ == "__main__":
    main()
