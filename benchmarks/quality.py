"""Paper Fig. 8 + §VIII-E: semantic vs vanilla overlap result quality.

Compares the k-th score of top-k semantic search against top-k vanilla
(exact-match) search and the intersection of the returned id sets —
semantic overlap surfaces sets vanilla search cannot find."""
from __future__ import annotations

import numpy as np

from repro.core import KoiosSearch, SearchParams
from repro.data import sample_queries
from repro.runtime.compile_cache import enable_compile_cache

from .common import world


def vanilla_topk(coll, query, k):
    """Exact-match overlap |Q ∩ C| top-k (the classic JOSIE-style measure)."""
    q = set(np.asarray(query).tolist())
    scores = np.array([len(q.intersection(coll.get_set(i).tolist()))
                       for i in range(coll.num_sets)], np.int64)
    ids = np.argsort(-scores, kind="stable")[:k]
    return ids, scores[ids]


def run(datasets=("dblp", "opendata"), n_queries=2, k=10, alpha=0.8):
    rows = []
    params = SearchParams(k=k, alpha=alpha)
    for ds in datasets:
        coll, sim = world(ds)
        engine = KoiosSearch(coll, sim, params)
        for qi, q in enumerate(sample_queries(coll, n_queries, seed=23)):
            sem = engine.search(q)
            van_ids, van_scores = vanilla_topk(coll, q, k)
            inter = len(set(sem.ids.tolist()) & set(van_ids.tolist()))
            # vanilla overlap of the semantic winners (Lemma 1 check)
            van_of_sem = [len(set(np.asarray(q).tolist())
                              & set(coll.get_set(int(i)).tolist()))
                          for i in sem.ids]
            rows.append({
                "dataset": ds, "query": qi, "|Q|": len(q),
                "kth_semantic": float(sem.lb[-1]) if len(sem.lb) else 0.0,
                "kth_vanilla": float(van_scores[-1]) if len(van_scores)
                else 0.0,
                "intersection": inter,
                "semantic_gain": float(np.mean(
                    [s - v for s, v in zip(sem.lb, van_of_sem)])),
            })
    return rows


def main():
    enable_compile_cache()
    print("dataset,query,|Q|,kth_semantic,kth_vanilla,intersection,"
          "semantic_gain")
    for r in run():
        print(f"{r['dataset']},{r['query']},{r['|Q|']},"
              f"{r['kth_semantic']:.2f},{r['kth_vanilla']:.2f},"
              f"{r['intersection']},{r['semantic_gain']:.2f}")


if __name__ == "__main__":
    main()
