"""Batched auction algorithm — the TPU-native exact verifier (DESIGN.md §2).

The paper verifies candidates with the (sequential) Hungarian algorithm on a
CPU thread pool and early-terminates a matching when the node-label sum (a
*dual* upper bound) drops below theta_lb (Lemma 8).  On TPU we use Bertsekas'
auction algorithm instead:

  * every bidding round is dense, branch-free linear algebra (profit matrix,
    per-row top-2, per-column max) — VPU/MXU work, `vmap`-able over a batch
    of candidate sets;
  * the auction maintains *prices* (dual variables); the dual objective
        D = sum_j p_j + sum_i max(0, max_j (w_ij - p_j))
    upper-bounds SO at every round (weak duality).  Lemma 8's early
    termination falls out: abort the moment D < theta_lb;
  * with eps-scaling down to eps_min, the final assignment's score P
    satisfies  P >= SO - nq * eps_min,  so [P, min(D, P + nq*eps_min)] is a
    valid (lb, ub) bracket for SO.  The search treats verification results as
    brackets; brackets that straddle a decision threshold are re-verified
    exactly (hungarian) — so the search stays exact.

Matching is *optional* (Def. 1): a virtual null object with value 0 and
permanent price 0 absorbs persons whose best profit is <= 0.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

_NEG = jnp.float32(-1e30)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["lb", "ub", "assign", "early_stopped", "rounds"],
    meta_fields=[])
@dataclasses.dataclass(frozen=True)
class AuctionResult:
    lb: jnp.ndarray          # (B,) primal score (== SO up to nq*eps)
    ub: jnp.ndarray          # (B,) dual bound   (>= SO, always valid)
    assign: jnp.ndarray      # (B, N) column per row; -1 unmatched/null
    early_stopped: jnp.ndarray  # (B,) bool — aborted by theta_lb (Lemma 8)
    rounds: jnp.ndarray      # (B,) int32 bidding rounds executed


def _auction_single(w, nq, nc, eps_schedule, theta_lb, max_rounds,
                    use_kernel: bool = False, interpret: bool = False):
    """One padded weight matrix (N, M); logical sizes (nq, nc) <= (N, M).

    The problem is embedded in the K x K zero-padded square matrix
    (K = max(N, M)) but only the nq *logical* rows ever bid — the zero
    padding rows have nothing to win and forcing them through the bidding
    (the historical square/perfect formulation) costs O(K - nq) extra
    rounds per phase, the auction analogue of the square-padding cost the
    nq-bounded Hungarian augmentation already eliminated
    (``hungarian._solve_rect_min(n_aug=nq)``).  Soundness of the
    nq-row form:

      * lb is the score of a feasible (optional) matching, so lb <= SO
        always;
      * the dual objective
            D = sum_j p_j + sum_i max(0, max_j (w_ij - p_j))
        upper-bounds SO for any nonneg prices (weak duality) — this is the
        Lemma-8 early-termination bound, unchanged;
      * at phase end every assigned row i satisfies eps-CS
        (profit_i >= best_i - eps).  Summing eps-CS against an optimal
        assignment sigma* gives
            SO <= lb + nq*eps + sum_{j in sigma*\\A} p_j
               <= lb + nq*eps + leftover,
        with leftover = the total price of columns left unassigned.  The
        phase-transition rules below (zero unmatched columns' prices,
        release eps-CS violators *with their column zeroed*, to a
        fixpoint) maintain the invariant that a positively-priced column
        is always assigned — within a phase a bid can only transfer a
        column, never abandon it, and prices only rise — so at
        convergence leftover == 0 and the bracket is nq-tight:
            ub - lb <= nq * eps_final
        (the contract tests/test_matching.py guards against Hungarian).
        ``leftover`` stays in the ub formula as a defensive term; if the
        invariant were ever broken the bracket would widen, never lie.
    """
    N, M = w.shape
    K = max(N, M)                    # square, zero-padded
    rows = jnp.arange(K)
    cols = jnp.arange(K)
    row_valid = rows < nq
    col_valid = cols < nc
    wm = jnp.zeros((K, K), dtype=jnp.float32)
    wm = wm.at[:N, :M].set(w.astype(jnp.float32))
    wm = jnp.where(row_valid[:, None] & col_valid[None, :],
                   jnp.maximum(wm, 0.0), 0.0)

    def dual_bound(prices):
        # D >= SO for any nonneg prices (weak duality); all entries finite.
        profits = wm - prices[None, :]
        best = jnp.max(profits, axis=1)
        return jnp.sum(prices) + jnp.sum(jnp.maximum(best, 0.0))

    def _cols_taken(assign):
        hit = jnp.zeros((K,), jnp.int32).at[jnp.clip(assign, 0, K - 1)].max(
            (assign >= 0).astype(jnp.int32))
        return hit > 0

    def phase(carry, eps):
        prev_assign, prev_eps, prices, ub_best, early, total_rounds = carry
        # Phase transition, in place of the classical reset-and-rebid:
        #   1. stale-price hygiene — a column that ended the previous phase
        #      unmatched keeps no price, and matched columns are rebated the
        #      previous eps (winning bids overshoot the competitive level by
        #      up to eps; carrying the overshoot strands columns that then
        #      attract no bids at smaller eps);
        #   2. the previous assignment is KEPT and rows whose eps-CS is
        #      violated at the new eps are released *with their column's
        #      price zeroed*, iterated to a fixpoint (zeroing a column can
        #      invalidate another row's eps-CS).  Resetting the assignment
        #      while keeping prices makes the nq-row form oscillate between
        #      phases, and releasing without zeroing strands price mass on
        #      abandoned columns (the historical square form hid both by
        #      having the zero rows re-absorb every column).
        # Both steps are sound for any nonneg prices: the dual bound is
        # price-history-free, and eps-CS is re-established here and then
        # preserved within the phase (alternative profits only fall as
        # prices rise; a held column's price is constant while held; a
        # column is only freed by eviction, which re-awards it).  The
        # invariant they buy: at phase end every positively-priced column
        # is assigned, so the optimality gap of the final assignment is
        # nq*eps with NO unassigned-price leftover.
        prices = jnp.where(_cols_taken(prev_assign),
                           jnp.maximum(prices - prev_eps, 0.0), 0.0)

        def rel_body(s):
            assign, prices, _ = s
            profits = wm - prices[None, :]
            best = jnp.max(profits, axis=1)
            held = jnp.clip(assign, 0, K - 1)
            viol = (assign >= 0) & (profits[rows, held] < best - eps)
            freed = jnp.zeros((K,), bool).at[held].max(viol)
            prices = jnp.where(freed, 0.0, prices)
            assign = jnp.where(viol, jnp.int32(-1), assign)
            return assign, prices, jnp.any(viol)

        assign0, prices, _ = jax.lax.while_loop(
            lambda s: s[2], rel_body,
            (prev_assign, prices, jnp.bool_(True)))

        def cond(s):
            assign, prices, ub_best, early, r = s
            unfinished = jnp.any((assign == -1) & row_valid)
            return unfinished & (~early) & (r < max_rounds)

        def body(s):
            assign, prices, ub_best, early, r = s
            if use_kernel:
                # fused subtract + per-row top-2 (kernels/auction_round.py):
                # the (K, K) profit matrix never materializes in HBM.  Same
                # first-index tie-breaking as the inline pass below.
                from ...kernels import ops as _kops
                w1, w2, jstar = _kops.auction_topk2(wm, prices,
                                                    interpret=interpret)
            else:
                profits = wm - prices[None, :]
                w1 = jnp.max(profits, axis=1)
                jstar = jnp.argmax(profits, axis=1).astype(jnp.int32)
                second = jnp.where(cols[None, :] == jstar[:, None], _NEG,
                                   profits)
                w2 = jnp.max(second, axis=1)
            bidding = (assign == -1) & row_valid
            bid_val = w1 + prices[jstar] - w2 + eps   # = w[i,j*] - w2 + eps

            # dense bid matrix: rows bid on their jstar only (gather-only
            # conflict resolution — no duplicate-index scatters)
            bid_mat = jnp.where(
                bidding[:, None] & (cols[None, :] == jstar[:, None]),
                bid_val[:, None], _NEG)
            col_best = jnp.max(bid_mat, axis=0)
            col_winner = jnp.argmax(bid_mat, axis=0).astype(jnp.int32)
            has_bid = col_best > _NEG / 2

            # eviction: person i loses its object if that object was re-awarded
            cur_j = jnp.clip(assign, 0, K - 1)
            holds = assign >= 0
            evict = holds & has_bid[cur_j] & (col_winner[cur_j] != rows)

            # award: person i wins iff it bid on jstar[i] and won the argmax
            won = bidding & has_bid[jstar] & (col_winner[jstar] == rows)

            assign = jnp.where(won, jstar,
                               jnp.where(evict, jnp.int32(-1), assign))
            prices = jnp.where(has_bid, col_best, prices)

            d = dual_bound(prices)
            ub_best = jnp.minimum(ub_best, d)
            early = early | (ub_best < theta_lb)
            return assign, prices, ub_best, early, r + 1

        assign, prices, ub_best, early, r = jax.lax.while_loop(
            cond, body, (assign0, prices, ub_best, early, jnp.int32(0)))
        return (assign, eps, prices, ub_best, early, total_rounds + r), None

    prices0 = jnp.zeros((K,), dtype=jnp.float32)
    ub0 = dual_bound(prices0)
    carry0 = (jnp.full((K,), -1, dtype=jnp.int32), jnp.float32(0.0),
              prices0, ub0, jnp.bool_(False), jnp.int32(0))
    (assign, _, prices, ub_best, early, rounds), _ = jax.lax.scan(
        phase, carry0, eps_schedule)
    converged = jnp.all((assign >= 0) | ~row_valid)

    matched = (assign >= 0) & row_valid
    gathered = wm[rows, jnp.clip(assign, 0, K - 1)]
    lb = jnp.sum(jnp.where(matched, gathered, 0.0))
    eps_final = eps_schedule[-1]
    # eps-CS slack is one eps per *logical* person plus the price mass of
    # unassigned columns (0 in the common case — see docstring).
    leftover = jnp.sum(jnp.where(_cols_taken(assign), 0.0, prices))
    ub = jnp.where(converged & ~early,
                   jnp.minimum(ub_best,
                               lb + nq.astype(jnp.float32) * eps_final
                               + leftover),
                   ub_best)
    # an early-stopped element's lb is not meaningful; its ub < theta_lb is.
    lb = jnp.where(early, 0.0, lb)
    return lb, jnp.maximum(ub, lb), assign[:N], early, rounds


def make_eps_schedule(eps_min: float, eps_start: float = 0.25,
                      factor: float = 0.2) -> jnp.ndarray:
    eps = []
    e = eps_start
    while e > eps_min:
        eps.append(e)
        e *= factor
    eps.append(eps_min)
    return jnp.asarray(eps, dtype=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("max_rounds", "use_kernel", "interpret"))
def auction_batch(w, nq, nc, eps_schedule, theta_lb, max_rounds: int = 5000,
                  use_kernel: bool = False, interpret: bool = False):
    """Batched verification.

    Args:
      w: (B, N, M) padded weight matrices (alpha-thresholded, in [0, 1]).
      nq, nc: (B,) logical sizes.
      eps_schedule: (P,) descending epsilons from :func:`make_eps_schedule`.
      theta_lb: pruning threshold (Lemma 8) — scalar, or (B,) per-element
        when one batch carries several queries' verifications (the shared
        multi-query verify queue); use -inf to disable.
      use_kernel: run each round's profit top-2 through the fused Pallas
        kernel (``kernels/auction_round.py``) — the TPU serving/fused-wave
        path; the default inline jnp pass is the same math (guarded by a
        parity test) and faster under CPU interpret mode.
      interpret: run that kernel in Pallas interpret mode (tests off-TPU).
    Returns :class:`AuctionResult` of per-element score brackets.
    """
    theta = jnp.broadcast_to(
        jnp.asarray(theta_lb, jnp.float32), nq.shape)
    fn = jax.vmap(
        lambda wi, nqi, nci, ti: _auction_single(
            wi, nqi, nci, eps_schedule, ti, max_rounds,
            use_kernel=use_kernel, interpret=interpret))
    lb, ub, assign, early, rounds = fn(w, nq, nc, theta)
    return AuctionResult(lb=lb, ub=ub, assign=assign,
                         early_stopped=early, rounds=rounds)


def auction_score_bounds(w, eps_min: float = 1e-4, theta_lb: float = -1e30):
    """Single-matrix convenience wrapper; returns (lb, ub)."""
    w = jnp.asarray(w, dtype=jnp.float32)
    nq = jnp.int32(w.shape[0])
    nc = jnp.int32(w.shape[1])
    res = auction_batch(w[None], nq[None], nc[None],
                        make_eps_schedule(eps_min),
                        jnp.float32(theta_lb))
    return res.lb[0], res.ub[0]
