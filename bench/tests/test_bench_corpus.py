"""The benchmark's corpus copy matches Table I and is fixed by its seed."""
import numpy as np
import pytest

import _paths  # noqa: F401
import corpus

# Table I of the KOIOS paper: sets, avg, max, vocab; Zipf exponent of
# the generator.  The original generator (src/repro/data/sets.py) gives
# avg 22.0 for Twitter and 172.8 for DBLP: within 3.5% of Table I.
TABLE_I = {"twitter": (27204, 22.6, 151, 72910, 1.1),
           "dblp": (4246, 178.7, 514, 25159, 1.05)}


@pytest.mark.parametrize("name", sorted(TABLE_I))
def test_corpus_matches_table_one(name):
    n, avg, mx, vocab, a = TABLE_I[name]
    indptr, tokens = corpus.make_corpus(n, vocab, avg, mx, a, 0)
    sizes = np.diff(indptr)
    assert len(sizes) == n
    assert sizes.max() <= mx and sizes.min() >= 2
    assert abs(sizes.mean() - avg) / avg < 0.035
    assert tokens.min() >= 0 and tokens.max() < vocab
    assert len(np.unique(tokens)) > 0.5 * vocab
    for i in range(0, n, max(1, n // 500)):
        s = tokens[indptr[i]:indptr[i + 1]]
        assert len(np.unique(s)) == len(s)
    again = corpus.make_corpus(n, vocab, avg, mx, a, 0)
    assert np.array_equal(indptr, again[0])
    assert np.array_equal(tokens, again[1])


def test_sizes_match_the_original_generator():
    from repro.data.sets import PRESETS, _sizes

    spec = PRESETS["dblp"]
    want = _sizes(spec, spec.num_sets, np.random.default_rng(0))
    got = corpus.set_sizes(spec.num_sets, spec.avg_size, spec.max_size,
                           np.random.default_rng(0))
    assert np.array_equal(want, got)


def test_token_popularity_is_zipf():
    indptr, tokens = corpus.make_corpus(4000, 5000, 20.0, 60, 1.1, 3)
    counts = np.sort(np.bincount(tokens, minlength=5000))[::-1]
    # heavy head: the most popular token is in a large share of sets,
    # and popularity falls by rank
    assert counts[0] > 0.2 * 4000
    assert counts[0] > counts[10] > counts[100] > counts[1000]


def test_embeddings_structure_fixed_values_from_seed():
    a = corpus.make_embeddings(2000, 300, structure_seed=0, value_seed=1)
    b = corpus.make_embeddings(2000, 300, structure_seed=0, value_seed=2)
    c = corpus.make_embeddings(2000, 300, structure_seed=0, value_seed=1)
    assert a.dtype == np.float32 and a.shape == (2000, 300)
    assert np.allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-5)
    assert np.array_equal(a, c) and not np.allclose(a, b)
    na = (a @ a.T) >= 0.8
    nb = (b @ b.T) >= 0.8
    # the same neighbourhoods (clusters) whatever the value seed
    assert (na != nb).sum() <= 0.001 * na.sum()
    assert 2.0 < na.sum(axis=1).mean() < 8.0
