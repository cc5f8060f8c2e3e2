"""Matching-layer tests: greedy / Hungarian / auction vs the scipy oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from scipy.optimize import linear_sum_assignment

from repro.core.matching import (auction_batch, auction_score_bounds,
                                 greedy_matching_score, hungarian_batch,
                                 hungarian_score, make_eps_schedule)
from repro.core.types import lane_cover


def _oracle(w):
    ri, ci = linear_sum_assignment(-w)
    return float(w[ri, ci].sum())


def _random_weights(rng, nq, nc, thresh):
    w = rng.random((nq, nc)).astype(np.float32)
    return np.where(w >= thresh, w, 0.0)


# ---------------------------------------------------------------- hungarian
@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("shape", [(1, 1), (3, 7), (7, 3), (12, 12)])
def test_hungarian_exact(seed, shape):
    rng = np.random.default_rng(seed)
    w = _random_weights(rng, *shape, thresh=0.5)
    assert abs(float(hungarian_score(jnp.asarray(w))) - _oracle(w)) < 1e-4


def test_hungarian_batch_padded():
    rng = np.random.default_rng(3)
    B, N, M = 6, 10, 14
    w = np.zeros((B, N, M), np.float32)
    nqs = rng.integers(1, N + 1, B).astype(np.int32)
    ncs = rng.integers(1, M + 1, B).astype(np.int32)
    oracles = []
    for b in range(B):
        wb = _random_weights(rng, nqs[b], ncs[b], 0.6)
        w[b, :nqs[b], :ncs[b]] = wb
        oracles.append(_oracle(wb))
    so, _, _ = hungarian_batch(jnp.asarray(w), jnp.asarray(nqs),
                               jnp.asarray(ncs))
    np.testing.assert_allclose(np.asarray(so), oracles, atol=1e-4)


# (B, logical |Q| range, logical |C| range, widest set, weight threshold):
# rows padded to lane_cover(|Q|), columns to lane_cover(widest set), as the
# verifier pool pads them
RECT_CASES = {
    "nq_eq_nc": (3, (12, 12), (12, 12), 12, 0.5),
    "nq_much_less_than_nc": (3, (2, 6), (40, 60), 60, 0.5),
    "zero_rows": (4, (0, 0), (5, 30), 30, 0.5),
    "all_below_alpha": (3, (4, 9), (4, 20), 20, 1.01),
    "width_129": (2, (20, 129), (100, 129), 129, 0.6),
    "width_257": (2, (30, 257), (200, 257), 257, 0.6),
    "width_514": (2, (60, 163), (400, 514), 514, 0.7),
}


def _rect_block(case):
    """The padded batch (w (B, R, C), nqs, ncs) of a ``RECT_CASES`` case."""
    B, (q_lo, q_hi), (c_lo, c_hi), widest, thresh = RECT_CASES[case]
    rng = np.random.default_rng(sorted(RECT_CASES).index(case))
    nqs = rng.integers(q_lo, q_hi + 1, B).astype(np.int32)
    ncs = rng.integers(c_lo, c_hi + 1, B).astype(np.int32)
    nqs[0], ncs[0] = q_hi, c_hi
    R, C = lane_cover(max(q_hi, 1), 8), lane_cover(widest, 8)
    w = np.zeros((B, R, C), np.float32)
    for b in range(B):
        w[b, :nqs[b], :ncs[b]] = _random_weights(rng, nqs[b], ncs[b], thresh)
    return w, nqs, ncs


def _square_solve(w, nq, nc):
    """The square solver ``_hungarian_padded`` ran before its rectangle
    form (kept here as the comparison): -w zero-padded to (n, n),
    n = max(R, C), the first nq rows augmented, each Dijkstra scan taking
    the lowest-index column among ties.  Returns (SO, col4row)."""
    n = max(w.shape)
    cost = jnp.zeros((n, n), jnp.float32).at[:w.shape[0], :w.shape[1]].set(
        -w.astype(jnp.float32))
    valid = (jnp.arange(n) < nq)[:, None] & (jnp.arange(n) < nc)[None, :]
    cost = jnp.where(valid, cost, 0.0)
    rows = jnp.arange(n)
    inf = jnp.float32(1e30)

    def augment(cur_row, carry):
        u, v, row4col, col4row = carry

        def scan_body(s):
            shortest, path, SR, SC, sink, i, min_val = s
            SR = SR.at[i].set(True)
            d = min_val + cost[i, :] - u[i] - v
            upd = (~SC) & (d < shortest)
            shortest = jnp.where(upd, d, shortest)
            path = jnp.where(upd, i, path)
            masked = jnp.where(SC, inf, shortest)
            j = jnp.argmin(masked).astype(jnp.int32)
            min_val = masked[j]
            SC = SC.at[j].set(True)
            free = row4col[j] < 0
            return (shortest, path, SR, SC, jnp.where(free, j, -1),
                    jnp.where(free, i, row4col[j]), min_val)

        init = (jnp.full((n,), inf), jnp.full((n,), -1, jnp.int32),
                jnp.zeros((n,), bool), jnp.zeros((n,), bool), jnp.int32(-1),
                jnp.int32(cur_row), jnp.float32(0.0))
        shortest, path, SR, SC, sink, _, min_val = jax.lax.while_loop(
            lambda s: s[4] < 0, scan_body, init)
        u = u + jnp.where(SR, jnp.where(
            rows == cur_row, min_val,
            min_val - shortest[jnp.clip(col4row, 0, n - 1)]), 0.0)
        v = v - jnp.where(SC, min_val - shortest, 0.0)

        def aug_body(s):
            row4col, col4row, j, _ = s
            i = path[j]
            return (row4col.at[j].set(i), col4row.at[i].set(j), col4row[i],
                    i == cur_row)

        row4col, col4row, _, _ = jax.lax.while_loop(
            lambda s: ~s[3], aug_body,
            (row4col, col4row, sink, jnp.bool_(False)))
        return u, v, row4col, col4row

    carry = (jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
             jnp.full((n,), -1, jnp.int32), jnp.full((n,), -1, jnp.int32))
    _, _, _, col4row = jax.lax.fori_loop(0, nq, augment, carry)
    total = jnp.sum(jnp.where(col4row >= 0,
                              cost[rows, jnp.clip(col4row, 0, n - 1)], 0.0))
    return -total, col4row


@pytest.mark.parametrize("case", sorted(RECT_CASES))
def test_rectangular_solve_equals_scipy_and_the_square_solve(case):
    """The solver works on the (R, max(R, C)) rectangle of a block padded
    by the cover rule.  Its scores equal SciPy's and, bit for bit, those
    of the square solver it replaced (``_square_solve``), and so does the
    weight of its matching; it leaves rows without an edge unmatched.
    Its lockstep scan count is at least one scan per augmented row."""
    thresh = RECT_CASES[case][-1]
    w, nqs, ncs = _rect_block(case)
    B, R, C = w.shape
    oracles = [_oracle(w[b, :nqs[b], :ncs[b]]) if nqs[b] * ncs[b] else 0.0
               for b in range(B)]
    so, col4row, steps = hungarian_batch(jnp.asarray(w), jnp.asarray(nqs),
                                         jnp.asarray(ncs))
    so, col4row = np.asarray(so), np.asarray(col4row)
    np.testing.assert_allclose(so, oracles, rtol=1e-6, atol=1e-5)
    for b in range(B):
        so_sq, col4row_sq = _square_solve(jnp.asarray(w[b]), int(nqs[b]),
                                          int(ncs[b]))
        assert so[b] == np.float32(so_sq)
        got = [w[b, i, j] for i, j in enumerate(col4row[b]) if j >= 0]
        was = [w[b, i, j] for i, j in enumerate(np.asarray(col4row_sq)[:R])
               if 0 <= j < C]
        assert np.float64(got).sum() == np.float64(was).sum()
    with_edge = (w > 0).any(axis=2)
    assert (col4row[~with_edge] == -1).all()
    assert int(steps) >= int(with_edge.sum(axis=1).max())
    if thresh > 1.0:
        assert not so.any() and int(steps) == 0


def _all_ties_block():
    """The block of ``test_ties_end_each_scan_at_a_free_column``."""
    B, nq, nc = 2, 40, 64
    w = np.zeros((B, lane_cover(nq, 8), lane_cover(nc, 8)), np.float32)
    w[:, :nq, :nc] = 0.9
    return w, np.full(B, nq, np.int32), np.full(B, nc, np.int32)


def test_ties_end_each_scan_at_a_free_column():
    """Every edge of a block weighs the same, so each augmentation's
    distances tie across all columns: a scan that takes a free column
    among the ties ends at once, one scan per row, where taking the
    lowest-index column walks the matched ones first."""
    w, nqs, ncs = _all_ties_block()
    nq = int(nqs[0])
    so, _, steps = hungarian_batch(jnp.asarray(w), jnp.asarray(nqs),
                                   jnp.asarray(ncs))
    np.testing.assert_allclose(np.asarray(so), 0.9 * nq, rtol=1e-6)
    assert int(steps) == nq


def _gather_dual_solve(w, nqs, ncs):
    """The rectangle solver as it was before it recorded each row's entry
    distance (kept here as the comparison): the dual update gathers
    ``shortest`` through ``col4row``, and the loops read and write at
    per-row indices.  Returns (SO (B,), col4row (B, R), steps) like
    ``hungarian_batch``."""
    from repro.core.matching.hungarian import _INF, _rect_cost

    def solve(w, nq, nc):
        cost = _rect_cost(w, nq, nc)
        R, C = cost.shape
        rows = jnp.arange(R)
        has_edge = jnp.any(cost < 0, axis=1)

        def augment(cur_row, carry):
            u, v, row4col, col4row, scans = carry
            active = (cur_row < nq) & has_edge[cur_row]

            def scan_body(s):
                shortest, path, SR, SC, sink, i, min_val, n = s
                SR = SR.at[i].set(True)
                d = min_val + cost[i, :] - u[i] - v
                upd = (~SC) & (d < shortest)
                shortest = jnp.where(upd, d, shortest)
                path = jnp.where(upd, i, path)
                masked = jnp.where(SC, _INF, shortest)
                min_val = jnp.min(masked)
                at_min = masked == min_val
                free_min = at_min & (row4col < 0)
                j = jnp.where(jnp.any(free_min), jnp.argmax(free_min),
                              jnp.argmax(at_min)).astype(jnp.int32)
                SC = SC.at[j].set(True)
                free = row4col[j] < 0
                return (shortest, path, SR, SC, jnp.where(free, j, -1),
                        jnp.where(free, i, row4col[j]), min_val, n + 1)

            init = (jnp.full((C,), _INF), jnp.full((C,), -1, jnp.int32),
                    jnp.zeros((R,), bool), jnp.zeros((C,), bool),
                    jnp.int32(-1), jnp.int32(cur_row), jnp.float32(0.0),
                    jnp.int32(0))
            shortest, path, SR, SC, sink, _, min_val, n = jax.lax.while_loop(
                lambda s: (s[4] < 0) & active, scan_body, init)
            u = u + jnp.where(SR, jnp.where(
                rows == cur_row, min_val,
                min_val - shortest[jnp.clip(col4row, 0, C - 1)]), 0.0)
            v = v - jnp.where(SC, min_val - shortest, 0.0)

            def aug_body(s):
                row4col, col4row, j, _ = s
                i = path[j]
                return (row4col.at[j].set(i), col4row.at[i].set(j),
                        col4row[i], i == cur_row)

            row4col, col4row, _, _ = jax.lax.while_loop(
                lambda s: ~s[3], aug_body, (row4col, col4row, sink, ~active))
            return u, v, row4col, col4row, scans.at[cur_row].set(n)

        carry = (jnp.zeros((R,), jnp.float32), jnp.zeros((C,), jnp.float32),
                 jnp.full((C,), -1, jnp.int32), jnp.full((R,), -1, jnp.int32),
                 jnp.zeros((R,), jnp.int32))
        _, _, _, col4row, scans = jax.lax.fori_loop(0, nq, augment, carry)
        total = jnp.sum(jnp.where(
            col4row >= 0, cost[rows, jnp.clip(col4row, 0, C - 1)], 0.0))
        return -total, col4row, scans

    so, col4row, scans = jax.jit(jax.vmap(solve))(w, nqs, ncs)
    return so, col4row, jnp.sum(jnp.max(scans, axis=0))


def _dblp_block():
    """Two DBLP-shaped blocks: 640 x 640 (the lane cover of a 514-token
    set), 514 and 300 logical rows against 514 and 420 columns."""
    rng = np.random.default_rng(18)
    nqs, ncs = np.int32([514, 300]), np.int32([514, 420])
    w = np.zeros((2, 640, 640), np.float32)
    for b in range(2):
        w[b, :nqs[b], :ncs[b]] = _random_weights(rng, nqs[b], ncs[b], 0.8)
    return w, nqs, ncs


@pytest.mark.parametrize(
    "case", sorted(RECT_CASES) + ["all_ties", "dblp_640"])
def test_entry_distance_dual_equals_the_gather(case):
    """The dual update reads each reached row's entry distance, recorded
    during the scan, where it gathered ``shortest[col4row]``, and the
    loops index by masked reductions and selects: the solves agree bit
    for bit, scores, matchings and lockstep scans."""
    w, nqs, ncs = {"all_ties": _all_ties_block,
                   "dblp_640": _dblp_block}.get(
        case, lambda: _rect_block(case))()
    args = jnp.asarray(w), jnp.asarray(nqs), jnp.asarray(ncs)
    got = [np.asarray(x) for x in hungarian_batch(*args)]
    was = [np.asarray(x) for x in _gather_dual_solve(*args)]
    for g, e in zip(got, was):
        assert g.dtype == e.dtype and g.shape == e.shape
        np.testing.assert_array_equal(g, e)


@pytest.fixture(scope="module")
def solver_hlo():
    sds = jax.ShapeDtypeStruct
    return hungarian_batch.lower(
        sds((4, 64, 64), jnp.float32), sds((4,), jnp.int32),
        sds((4,), jnp.int32)).compile().as_text().splitlines()


def test_the_dual_update_gathers_nothing(solver_hlo):
    """No instruction of the compiled solver's dual update is a gather:
    under ``vmap`` the gather through ``col4row`` was an element-wise
    gather fusion that took 80% of a DBLP shard wave on the chip."""
    dual = [ln for ln in solver_hlo if "solver.dual" in ln]
    assert dual, "the solver.dual scope is missing from the compiled solver"
    assert not [ln for ln in dual if "gather" in ln]


@pytest.mark.parametrize("scope,banned", [
    ("solver.scan", ("scatter",)),
    ("solver.augment", ("gather", "scatter"))])
def test_the_solver_loops_index_by_selects(solver_hlo, scope, banned):
    """Under ``vmap`` every read or write at a per-row index is its own
    gather or scatter op, with a copy of the array before each scatter:
    the loops read with masked reductions and write with selects instead,
    so each augmentation runs fewer, larger device ops.  The scan keeps
    one gather, the cost row of the row it extends."""
    ops = [ln for ln in solver_hlo if scope in ln]
    assert ops, f"the {scope} scope is missing from the compiled solver"
    assert not [ln for ln in ops if any(b in ln for b in banned)]


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 9), st.integers(1, 9))
def test_hungarian_property(seed, nq, nc):
    rng = np.random.default_rng(seed)
    w = _random_weights(rng, nq, nc, 0.4)
    assert abs(float(hungarian_score(jnp.asarray(w))) - _oracle(w)) < 1e-4


# ------------------------------------------------------------------- greedy
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 9), st.integers(1, 9))
def test_greedy_bounds(seed, nq, nc):
    """Greedy is a lower bound and a 1/2-approximation (Lemma 3)."""
    rng = np.random.default_rng(seed)
    w = _random_weights(rng, nq, nc, 0.3)
    so = _oracle(w)
    g = float(greedy_matching_score(jnp.asarray(w)))
    assert g <= so + 1e-5
    assert g >= so / 2 - 1e-5


# ------------------------------------------------------------------ auction
def test_auction_exact_brackets():
    rng = np.random.default_rng(0)
    for _ in range(10):
        nq, nc = rng.integers(1, 16, 2)
        w = _random_weights(rng, nq, nc, 0.6)
        so = _oracle(w)
        lb, ub = auction_score_bounds(w, eps_min=1e-4)
        K = max(nq, nc)
        assert float(lb) <= so + 1e-4
        assert float(ub) >= so - 1e-4
        assert float(ub) - float(lb) <= K * 2e-4 + 1e-4


def test_auction_early_termination_lemma8():
    """theta_lb above every SO -> every matching aborted with a certificate."""
    rng = np.random.default_rng(1)
    B, N, M = 4, 12, 12
    w = np.stack([_random_weights(rng, N, M, 0.6) for _ in range(B)])
    nqs = np.full(B, N, np.int32)
    ncs = np.full(B, M, np.int32)
    res = auction_batch(jnp.asarray(w), jnp.asarray(nqs), jnp.asarray(ncs),
                        make_eps_schedule(1e-4), jnp.float32(1e9))
    assert bool(np.all(np.asarray(res.early_stopped)))
    # the certificate: dual bound below theta at abort
    assert bool(np.all(np.asarray(res.ub) < 1e9))


def test_auction_dual_always_upper_bound():
    """ub >= SO even when theta_lb triggers early termination mid-way."""
    rng = np.random.default_rng(2)
    for _ in range(5):
        nq, nc = rng.integers(3, 12, 2)
        w = _random_weights(rng, nq, nc, 0.5)
        so = _oracle(w)
        # theta slightly below SO: must NOT abort (ub never sinks below SO)
        lb, ub = auction_score_bounds(w, eps_min=1e-4, theta_lb=so - 0.05)
        assert float(ub) >= so - 1e-4
        assert float(lb) <= so + 1e-4


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 12), st.integers(1, 24))
def test_auction_nq_bounded_exact_vs_hungarian(seed, nq, nc):
    """Guards the nq-row auction (only logical |Q| rows bid; release-with-
    price-zeroing phase transitions): brackets must contain the exact
    Hungarian SO and be nq-tight — the bracket's eps-CS slack is one eps
    per LOGICAL row, with no unassigned-price leftover."""
    rng = np.random.default_rng(seed)
    w = _random_weights(rng, nq, nc, 0.5)
    N, M = max(nq, 4), max(nc, 4)          # padded shapes, like the pool's
    wp = np.zeros((N, M), np.float32)
    wp[:nq, :nc] = w
    so, _, _ = hungarian_batch(jnp.asarray(wp)[None],
                               jnp.asarray([nq], jnp.int32),
                               jnp.asarray([nc], jnp.int32))
    so = float(so[0])
    res = auction_batch(jnp.asarray(wp)[None], jnp.asarray([nq], jnp.int32),
                        jnp.asarray([nc], jnp.int32),
                        make_eps_schedule(1e-4), jnp.float32(-1e30))
    lb, ub = float(res.lb[0]), float(res.ub[0])
    assert lb <= so + 1e-4 <= ub + 2e-4
    assert ub - lb <= nq * 2e-4 + 1e-4     # nq-bounded slack, NOT max(N, M)


def test_auction_rounds_bounded_by_logical_rows():
    """The square-padding round cost is gone: a |Q|=1 verification against
    a wide padded matrix converges in O(phases) rounds, not O(K)."""
    rng = np.random.default_rng(9)
    K = 64
    w = np.zeros((1, K, K), np.float32)
    w[0, 0] = np.where(rng.random(K) >= 0.5, rng.random(K), 0.0)
    res = auction_batch(jnp.asarray(w), jnp.asarray([1], jnp.int32),
                        jnp.asarray([K], jnp.int32),
                        make_eps_schedule(1e-4), jnp.float32(-1e30))
    assert int(res.rounds[0]) < K // 2     # historical form needed >= K


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 10), st.integers(1, 10))
def test_auction_vs_scipy(seed, nq, nc):
    """Guards the square/perfect-matching reduction (DESIGN.md §2): the
    asymmetric dummy-sink form breaks eps-scaling price carryover."""
    rng = np.random.default_rng(seed)
    w = _random_weights(rng, nq, nc, 0.5)
    so = _oracle(w)
    lb, ub = auction_score_bounds(w, eps_min=1e-4)
    assert float(lb) <= so + 1e-4 <= float(ub) + 2e-4
    assert float(ub) - float(lb) <= max(nq, nc) * 2e-4 + 1e-4
