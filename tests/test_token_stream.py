"""Token stream & inverted index invariants (paper §IV)."""
import numpy as np

from repro.core import InvertedIndex, build_token_stream, expand_to_events
from repro.core.token_stream import pad_events
from repro.data import sample_queries


def test_stream_complete_and_sorted(small_world):
    """Every (q, t) pair with sim >= alpha appears exactly once, descending."""
    coll, sim = small_world
    q = sample_queries(coll, 1, seed=3)[0]
    alpha = 0.8
    stream = build_token_stream(q, sim, alpha)
    # descending
    assert np.all(np.diff(stream.sim) <= 1e-6)
    assert np.all(stream.sim >= alpha - 1e-6)
    # completeness vs dense similarity
    dense = np.asarray(sim.pairwise(q, np.arange(coll.vocab_size)))
    qi, tj = np.nonzero(dense >= alpha)
    want = set(zip(qi.tolist(), tj.tolist()))
    got = set(zip(stream.q_pos.tolist(), stream.token.tolist()))
    assert want == got
    # identity pairs carry sim exactly 1
    ident = q[stream.q_pos] == stream.token
    assert np.all(stream.sim[ident] == 1.0)


def test_inverted_index_roundtrip(small_world):
    coll, _ = small_world
    inv = InvertedIndex.build(coll)
    assert inv.total_postings == coll.total_tokens
    # spot-check: postings of token t are exactly the sets containing t
    rng = np.random.default_rng(0)
    for t in rng.integers(0, coll.vocab_size, 20):
        sets, slots = inv.postings(int(t))
        expect = [i for i in range(coll.num_sets)
                  if t in coll.get_set(i)]
        assert sorted(sets.tolist()) == expect
        # slots point back at this token in the flat array
        assert np.all(coll.set_tokens[slots] == t)


def test_event_expansion(small_world):
    coll, sim = small_world
    inv = InvertedIndex.build(coll)
    q = sample_queries(coll, 1, seed=5)[0]
    stream = build_token_stream(q, sim, 0.8)
    ev = expand_to_events(stream, inv)
    # events remain descending and reference valid sets
    assert np.all(np.diff(ev.sim) <= 1e-6)
    assert ev.set_id.min() >= 0 and ev.set_id.max() < coll.num_sets
    # event count == sum of posting counts over stream tokens
    counts = inv.posting_counts()
    assert len(ev) == int(counts[stream.token].sum())


def test_pad_events_pow2(small_world):
    coll, sim = small_world
    inv = InvertedIndex.build(coll)
    q = sample_queries(coll, 1, seed=5)[0]
    ev = expand_to_events(build_token_stream(q, sim, 0.8), inv)
    s, qp, sl, si = pad_events(ev, 64)
    n_chunks = s.shape[0]
    assert n_chunks & (n_chunks - 1) == 0          # power of two
    assert s.shape == qp.shape == sl.shape == si.shape
    flat = s.reshape(-1)
    assert np.all(flat[len(ev):] == -1)            # padding sentinel
    # padded sims keep the stream's final value (valid s_now)
    assert np.all(si.reshape(-1)[len(ev):] == ev.sim[-1])


def test_kernel_stream_parity(small_world):
    """``use_kernel=True`` routes the stream sweep through the
    ``cosine_topk`` Pallas kernel (interpret mode on CPU); the resulting
    streams must be bit-identical to the jnp provider path — same tuples,
    same values, same order (admission order is load-bearing)."""
    from repro.core.token_stream import build_token_stream_batch

    coll, sim = small_world
    queries = sample_queries(coll, 4, seed=5)
    for alpha in (0.8, 0.95):
        provider = build_token_stream_batch(queries, sim, alpha)
        kernel = build_token_stream_batch(queries, sim, alpha,
                                          use_kernel=True, interpret=True)
        for a, b in zip(provider, kernel):
            assert np.array_equal(a.q_pos, b.q_pos)
            assert np.array_equal(a.token, b.token)
            assert np.array_equal(a.sim, b.sim)


def test_kernel_stream_end_to_end(small_world):
    """A full engine run with ``stream_use_kernel`` (interpret mode, asked
    for with ``fused='interpret'``) returns bit-identical results (the
    stream feeds every downstream bound)."""
    from repro.core import KoiosSearch, SearchParams

    coll, sim = small_world
    queries = sample_queries(coll, 3, seed=17)
    base = KoiosSearch(coll, sim, SearchParams(k=5, alpha=0.8, chunk_size=64,
                                               verify_batch=8), partitions=2)
    kern = KoiosSearch(coll, sim, SearchParams(k=5, alpha=0.8, chunk_size=64,
                                               verify_batch=8,
                                               stream_use_kernel=True,
                                               fused="interpret"),
                       partitions=2)
    for a, b in zip(base.search_batch(queries), kern.search_batch(queries)):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)
        assert np.array_equal(a.ub, b.ub)
