"""Quickstart: top-k semantic overlap search in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import EmbeddingSimilarity, KoiosSearch, SearchParams
from repro.data import make_collection, make_embeddings, sample_queries

# 1. A repository of token sets (generate a synthetic one here; any CSR
#    SetCollection works — e.g. the distinct values of your table columns).
coll = make_collection(num_sets=500, vocab_size=4000, avg_size=10,
                       max_size=40, seed=0)

# 2. A similarity provider: cosine over an embedding table.  Swap in your
#    own vectors (FastText, a trained tower, ...) — KOIOS only needs
#    sim(x, x) = 1 and symmetry (paper Def. 1).
table = make_embeddings(coll.vocab_size, dim=64, seed=0)
sim = EmbeddingSimilarity(table)

# 3. Search.  alpha is the element-similarity threshold, k the result size.
engine = KoiosSearch(coll, sim, SearchParams(k=5, alpha=0.8))
query = sample_queries(coll, 1, seed=42)[0]
result = engine.search(query)

print(f"query |Q|={len(query)}: {query[:8]}...")
for rank, (sid, score) in enumerate(zip(result.ids, result.lb), 1):
    overlap = len(np.intersect1d(query, coll.get_set(int(sid))))
    print(f"  #{rank} set {sid:4d}  SO={score:6.2f}  "
          f"(vanilla overlap {overlap})")
st = result.stats
print(f"\ncandidates={st.candidates}  pruned_refinement="
      f"{st.pruned_refinement}  verified={st.exact_matches}  "
      f"no_em={st.pruned_no_em}")
print("=> the paper's claim in action: only "
      f"{100*st.exact_matches/max(st.candidates,1):.1f}% of candidates "
      "needed an exact graph matching")

# 4. Batched serving: many queries through ONE fused pipeline — a single
#    stacked similarity sweep and a shared cross-query verification queue.
#    Results are bit-identical to per-query search().
queries = sample_queries(coll, 4, seed=43)
for i, res in enumerate(engine.search_batch(queries)):
    print(f"batched query {i} (|Q|={len(queries[i])}): "
          f"top ids={res.ids[:3].tolist()} "
          f"scores={[round(float(s), 2) for s in res.lb[:3]]}")
