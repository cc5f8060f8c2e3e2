"""Partitioned / distributed search (paper §VI scale-out).

Shows the shared-theta_lb mechanism of partitioned search: the plan runs
one wave per partition over every query, and each query's running k-th
score is carried from partition to partition, so later partitions prune
against the bound earlier ones raised (on a device mesh the exchange is
an all-reduce-max over the (pod, data) axes, DESIGN.md §5).  Searches at
1 and 4 partitions: identical results, and the theta_lb trace shows the
bound rising partition by partition.

    PYTHONPATH=src python examples/distributed_search.py
"""
import numpy as np

from repro.core import (EmbeddingSimilarity, KoiosSearch, SearchParams)
from repro.data import dataset_preset, make_embeddings, sample_queries

coll = dataset_preset("opendata", scale=0.02, seed=0)
emb = make_embeddings(coll.vocab_size, dim=32, seed=0)
sim = EmbeddingSimilarity(emb)
params = SearchParams(k=10, alpha=0.8)
queries = sample_queries(coll, 4, seed=5)
longest = int(np.argmax([len(q) for q in queries]))

print(f"corpus: {coll.num_sets} sets, vocab {coll.vocab_size}, "
      f"|Q|={[len(q) for q in queries]}")

reference = None
for parts in (1, 4):
    engine = KoiosSearch(coll, sim, params, partitions=parts)
    results = engine.search_batch(queries)
    if reference is None:
        reference = results
    for a, b in zip(reference, results):
        assert np.array_equal(a.ids, b.ids) and np.array_equal(a.lb, b.lb)
    st = engine.scheduler_stats
    res = results[0]
    print(f"\npartitions={parts}: top-3 scores="
          f"{[round(float(s), 2) for s in res.lb[:3]]} "
          f"(bit-identical to 1 partition)")
    print(f"  per-query: candidates={res.stats.candidates} "
          f"pruned={res.stats.pruned_refinement} "
          f"verified={res.stats.exact_matches}")
    print(f"  scheduler: {st.schedule} waves={st.waves} tiles={st.tiles} "
          f"rounds={st.rounds} fused_requests={st.fused_requests}")
    print(f"  theta_lb (query {longest}) after each partition wave: "
          f"{[round(float(t[longest]), 3) for t in st.theta_trace]}")

print("\nBoth wave kinds (host waves, fused device waves) are asserted "
      "bit-for-bit equal across partitions x batch x verifier modes in "
      "tests/test_scheduler.py; on a TPU mesh the bound exchange is an "
      "all-reduce-max over the (pod, data) axes "
      "(repro.runtime.sharding.all_reduce_max, DESIGN.md §5).")
