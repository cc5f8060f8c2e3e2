"""Paper Table II / IV / V: pruning power of the filters.

Per dataset (and optionally per query-cardinality interval): candidate
sets, iUB-filtered during refinement, No-EM acceptances, EM-early
terminations, and full exact matchings — the percentages the paper's
central claim rests on (<5% of candidates verified for medium/large
queries)."""
from __future__ import annotations

import numpy as np

from repro.core import KoiosSearch, SearchParams
from repro.data import sample_queries
from repro.runtime.compile_cache import enable_compile_cache

from .common import world


def run(datasets=("dblp", "opendata", "twitter", "wdc"), n_queries=3,
        k=10, alpha=0.8, by_cardinality=False, ub_mode="sound"):
    rows = []
    params = SearchParams(k=k, alpha=alpha, ub_mode=ub_mode)
    for ds in datasets:
        coll, sim = world(ds)
        engine = KoiosSearch(coll, sim, params)
        if by_cardinality:
            sizes = coll.set_sizes
            qs = np.unique(np.quantile(sizes, [0.25, 0.5, 0.75]))
            edges = [2.0] + [q for q in qs if q > 2] + [sizes.max() + 1.0]
            intervals = [(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])
                         if hi > lo]
        else:
            intervals = [None]
        for interval in intervals:
            queries = sample_queries(coll, n_queries, card_range=interval,
                                     seed=7)
            agg = {"candidates": 0, "iub_filtered": 0, "no_em": 0,
                   "em_early": 0, "em_full": 0, "post_ub": 0}
            for q in queries:
                res = engine.search(q)
                st = res.stats
                agg["candidates"] += st.candidates
                agg["iub_filtered"] += st.pruned_refinement
                agg["no_em"] += st.pruned_no_em
                agg["em_early"] += st.pruned_em_early
                agg["em_full"] += st.exact_matches
                agg["post_ub"] += st.pruned_postprocess
            nq = max(len(queries), 1)
            cand = max(agg["candidates"], 1)
            rows.append({
                "dataset": ds,
                "interval": (f"{int(interval[0])}-{int(interval[1])}"
                             if interval else "all"),
                "queries": len(queries),
                **{key: v / nq for key, v in agg.items()},
                "refine_prune_pct": 100 * agg["iub_filtered"] / cand,
                "verified_pct": 100 * agg["em_full"] / cand,
            })
    return rows


def main():
    enable_compile_cache()
    print("dataset,interval,candidates,iUB%,No-EM,EM-early,EM,verified%")
    for r in run():
        print(f"{r['dataset']},{r['interval']},{r['candidates']:.0f},"
              f"{r['refine_prune_pct']:.1f},{r['no_em']:.1f},"
              f"{r['em_early']:.1f},{r['em_full']:.1f},"
              f"{r['verified_pct']:.2f}")


if __name__ == "__main__":
    main()
