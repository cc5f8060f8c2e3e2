"""The float64 reference, the comparison that decides ``correct``, and
the lower-precision control coming out as not correct."""
import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

import _paths  # noqa: F401
import corpus
import reference

K, ALPHA, TOL = 5, 0.8, 1e-4


@pytest.fixture(scope="module")
def world():
    indptr, tokens = corpus.make_corpus(300, 900, 10.0, 30, 1.1, 0)
    emb = corpus.make_embeddings(900, 64, structure_seed=0, value_seed=5)
    return indptr, tokens, reference.normalize(emb)


def brute_force(indptr, tokens, sims):
    """Every set's matching, no bound, no band."""
    out = {}
    w_all = np.where(sims >= ALPHA, sims, 0.0)
    for sid in range(len(indptr) - 1):
        w = w_all[:, tokens[indptr[sid]:indptr[sid + 1]]]
        if w.any():
            r, c = linear_sum_assignment(w, maximize=True)
            out[sid] = float(w[r, c].sum())
    return out


def served(ref_scores, k=K):
    best = sorted(ref_scores.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [i for i, _ in best], [s for _, s in best]


@pytest.mark.parametrize("qsid", [3, 77, 150, 299])
def test_bounded_walk_equals_brute_force(world, qsid):
    indptr, tokens, e64 = world
    q = tokens[indptr[qsid]:indptr[qsid + 1]]
    sims = reference.sims_f64(e64, q)
    ref = reference.Reference(indptr, tokens, sims, ALPHA)
    lo, hi = ref.topk(K)
    want = sorted(brute_force(indptr, tokens, sims).values(),
                  reverse=True)[:K]
    assert np.allclose(lo, want) and np.allclose(hi, want)
    ids, scores = served(brute_force(indptr, tokens, sims))
    gap, errors, msgs = reference.compare(ids, scores, ref, K, TOL)
    assert gap < 1e-12 and errors == 0 and not msgs


def _case(world, qsid=77):
    indptr, tokens, e64 = world
    q = tokens[indptr[qsid]:indptr[qsid + 1]]
    sims = reference.sims_f64(e64, q)
    ids, scores = served(brute_force(indptr, tokens, sims))
    return reference.Reference(indptr, tokens, sims, ALPHA), ids, scores


def test_compare_catches_a_swapped_id(world):
    ref, ids, scores = _case(world)
    outside = next(s for s in range(300) if s not in ids
                   and ref.score(s)[1] < scores[-1] - 0.1)
    bad = ids[:-1] + [outside]
    _, errors, msgs = reference.compare(bad, scores, ref, K, TOL)
    assert errors >= 1 and msgs


def test_compare_catches_a_perturbed_score(world):
    ref, ids, scores = _case(world)
    bad = list(scores)
    bad[2] += 1e-3
    gap, errors, _ = reference.compare(ids, bad, ref, K, TOL)
    assert gap == pytest.approx(1e-3, rel=1e-6) and gap > TOL


def test_compare_catches_a_short_list(world):
    ref, ids, scores = _case(world)
    gap, errors, _ = reference.compare(ids[:-1], scores[:-1], ref, K, TOL)
    assert errors == 1 and gap == float("inf")


def test_band_gives_an_interval_at_alpha():
    sims = np.array([[ALPHA + 5e-7, 0.9]])
    ref = reference.Reference(np.array([0, 1, 2]), np.array([0, 1]),
                              sims, ALPHA)
    assert ref.score(0) == pytest.approx((0.0, ALPHA + 5e-7))
    assert ref.score(1) == pytest.approx((0.9, 0.9))


@pytest.mark.parametrize("qsid", [11, 140, 222])
def test_bf16_control_is_not_correct(world, qsid):
    """The control: the reference in the program's place, similarities
    in bfloat16.  Its answer has to fail the score limit."""
    indptr, tokens, e64 = world
    q = tokens[indptr[qsid]:indptr[qsid + 1]]
    ids, scores = reference.control_topk(reference.sims_bf16, e64, indptr,
                                         tokens, q, ALPHA, K)
    ref = reference.Reference(indptr, tokens, reference.sims_f64(e64, q),
                              ALPHA)
    gap, errors, _ = reference.compare(ids, scores, ref, K, TOL)
    assert gap > TOL or errors > 0


def test_bf16_rounding_matches_ml_dtypes():
    ml = pytest.importorskip("ml_dtypes")
    x = np.random.default_rng(0).standard_normal(10000).astype(np.float32)
    want = x.astype(ml.bfloat16).astype(np.float32)
    assert np.array_equal(reference._bf16(x), want)
