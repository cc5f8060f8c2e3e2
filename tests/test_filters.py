"""Filter soundness tests — including the paper's Lemma 6 counterexample."""
import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st
from scipy.optimize import linear_sum_assignment

from repro.core import SearchParams, SearchStats
from repro.core.filters import compute_iub, kth_largest, prune_mask
from repro.core.postprocess import PostprocessState, VerifyOutcome


def _oracle(w):
    ri, ci = linear_sum_assignment(-w)
    return float(w[ri, ci].sum())


def test_kth_largest():
    x = jnp.asarray([3.0, 1.0, 5.0, 2.0])
    assert float(kth_largest(x, 1)) == 5.0
    assert float(kth_largest(x, 3)) == 2.0
    assert float(kth_largest(x, 10)) == 1.0  # clamps to len


def test_paper_iub_counterexample():
    """The paper's Lemma 6 bound undershoots SO (DESIGN.md §8.5):
    greedy-blocked elements can be re-matched by the optimal matching at
    similarities above s_now."""
    w = np.zeros((3, 3), np.float32)
    w[0, 0] = 1.0
    w[0, 1] = 0.99
    w[1, 0] = 0.99
    w[2, 2] = 0.9
    so = _oracle(w)                      # 2.88
    # stream (desc): (q0,c0,1.0) admitted; 0.99s blocked; (q2,c2,.9) admitted
    S, l, s_now = 1.9, 2, 0.9
    iub_paper = S + min(3 - l, 3 - l) * s_now
    assert iub_paper < so - 1e-6, "expected the unsound bound to undershoot"
    # the corrected per-query-element bound stays valid
    T, d, cap = 1.0 + 0.99 + 0.9, 3, 3
    iub_sound = T + max(0, cap - d) * s_now
    assert iub_sound >= so - 1e-6


def _simulate_stream_bounds(w, alpha):
    """Replay the refinement admission on a dense matrix; yield the sound
    bound after every event and return final (T, d, S)."""
    nq, nc = w.shape
    pairs = [(w[i, j], i, j) for i in range(nq) for j in range(nc)
             if w[i, j] >= alpha]
    pairs.sort(key=lambda p: -p[0])
    qmatched = np.zeros(nq, bool)
    cmatched = np.zeros(nc, bool)
    qseen = np.zeros(nq, bool)
    S = T = 0.0
    d = l = 0
    cap = min(nq, nc)
    bounds = []
    for s, i, j in pairs:
        if not qseen[i]:
            qseen[i] = True
            T += s
            d += 1
        if not qmatched[i] and not cmatched[j]:
            qmatched[i] = cmatched[j] = True
            S += s
            l += 1
        bounds.append(T + max(0, cap - d) * s)
    bounds.append(T)      # stream exhausted: s_now term drops (sub-alpha = 0)
    return bounds, S


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 100_000), st.integers(1, 8), st.integers(1, 8),
       st.sampled_from([0.5, 0.7, 0.8]))
def test_sound_iub_never_undershoots(seed, nq, nc, alpha):
    """Property: iUB'(C) >= SO at every stream position (DESIGN.md §8.5),
    and the greedy partial score S <= SO (Lemma 5)."""
    rng = np.random.default_rng(seed)
    w = rng.random((nq, nc)).astype(np.float32)
    w = np.where(w >= alpha, w, 0.0)
    so = _oracle(w)
    bounds, S = _simulate_stream_bounds(w, alpha)
    assert S <= so + 1e-5
    for b in bounds:
        assert b >= so - 1e-5


def test_compute_iub_modes():
    S = jnp.asarray([1.0, 2.0])
    l = jnp.asarray([1, 2], jnp.int32)
    T = jnp.asarray([1.5, 2.5])
    d = jnp.asarray([2, 3], jnp.int32)
    cap = jnp.asarray([4, 4], jnp.int32)
    seen = jnp.asarray([True, False])
    paper = compute_iub(S, l, T, d, cap, 0.9, seen, "paper")
    sound = compute_iub(S, l, T, d, cap, 0.9, seen, "sound")
    assert abs(float(paper[0]) - (1.0 + 3 * 0.9)) < 1e-6
    assert abs(float(sound[0]) - (1.5 + 2 * 0.9)) < 1e-6
    assert float(paper[1]) > 1e30 and float(sound[1]) > 1e30  # unseen


def test_prune_mask_keeps_a_set_whose_lb_reaches_theta():
    """A bound that rounds below the set's own lower bound does not prune
    it; a set below theta on both bounds goes, and ties survive."""
    lb = jnp.asarray([5.0000005, 4.0, 5.0, 4.0], jnp.float32)
    ub = jnp.asarray([5.0, 4.5, 6.0, 5.0000005], jnp.float32)
    theta = jnp.float32(5.0000005)
    killed = prune_mask(ub, theta, jnp.ones(4, bool), lb)
    assert np.asarray(killed).tolist() == [False, True, False, False]


def test_the_set_that_raised_theta_survives_its_rounded_matching_score():
    """The k-th set's refinement lower bound (a float32 sum in stream
    order) lies an ulp above its float32 matching score.  Theta is raised
    to that lower bound; the exact ub that comes back is below it.  The
    set stays in the answer instead of being dropped by its own bound."""
    lb_ref = np.float32(66.398903)
    exact = np.nextafter(lb_ref, np.float32(0), dtype=np.float32)
    params = SearchParams(k=2, verify_batch=8)
    st = PostprocessState(np.arange(3, dtype=np.int32),
                          surv_ids=np.array([7, 9]),
                          surv_lb=np.array([80.0, lb_ref]),
                          surv_ub=np.array([80.0, 70.0]), theta_lb0=0.0,
                          params=params, stats=SearchStats())
    scores = {7: np.float32(80.0), 9: exact}
    while (req := st.next_request()) is not None:
        assert st.theta_lb == float(lb_ref)
        so = np.array([scores[i] for i in req.ids], np.float32)
        st.apply(VerifyOutcome(lb=so, ub=so.copy(),
                               early=np.zeros(len(so), bool),
                               n_full=len(so)))
    res = st.result()
    assert res.ids.tolist() == [7, 9]
