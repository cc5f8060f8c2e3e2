"""Exact maximum-weight bipartite matching in JAX (assignment problem).

Shortest-augmenting-path algorithm (Jonker–Volgenant as in Crouse 2016 /
scipy's ``linear_sum_assignment``), expressed with ``lax`` control flow so it
jits, vmaps (batched verification) and runs inside the distributed search
step.  O(n^3).

Semantic-overlap conventions (paper Def. 1):
  * maximization with an *optional* one-to-one matching;
  * weights are in [0, 1] after the alpha-threshold, sub-alpha edges are 0.

We reduce to square min-cost assignment on ``cost = -w`` padded with zeros:
all weights are >= 0, so padded/zero edges are exactly as good as leaving an
element unmatched, and SO == -mincost.  Padded batches (per-element logical
sizes nq/nc <= n) follow the same argument: padding rows/cols carry weight 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_INF = jnp.float32(1e30)


def _solve_square_min(cost: jnp.ndarray, n_aug=None):
    """Min-cost assignment on square ``cost`` (n, n).

    Returns (total_cost, col4row, u, v).  Duals (u, v) satisfy
    u[i] + v[j] <= cost[i, j] with equality on the matching.

    ``n_aug`` (static or traced, <= n) limits augmentation to the first
    ``n_aug`` rows.  When every row beyond ``n_aug`` is all-zero (the
    square padding of a rectangular problem), the restricted solve is
    exact for the *perfect* assignment too: zero rows extend any optimal
    matching of the real rows at zero cost.  Augmenting nq rows instead
    of n cuts the JV cost from O(n^3) to O(nq * n^2) — the common
    verification shape has |Q| << |C|.
    """
    n = cost.shape[0]
    rows = jnp.arange(n)
    if n_aug is None:
        n_aug = n

    def augment(cur_row, carry):
        u, v, row4col, col4row = carry

        # --- Dijkstra scan from cur_row ------------------------------------
        shortest = jnp.full((n,), _INF)
        path = jnp.full((n,), -1, dtype=jnp.int32)   # predecessor row per col
        SR = jnp.zeros((n,), dtype=bool)
        SC = jnp.zeros((n,), dtype=bool)

        def scan_cond(s):
            _, _, _, _, sink, *_ = s
            return sink < 0

        def scan_body(s):
            shortest, path, SR, SC, sink, i, min_val = s
            SR = SR.at[i].set(True)
            d = min_val + cost[i, :] - u[i] - v
            upd = (~SC) & (d < shortest)
            shortest = jnp.where(upd, d, shortest)
            path = jnp.where(upd, i, path)
            masked = jnp.where(SC, _INF, shortest)
            j = jnp.argmin(masked).astype(jnp.int32)
            min_val = masked[j]
            SC = SC.at[j].set(True)
            free = row4col[j] < 0
            sink = jnp.where(free, j, jnp.int32(-1))
            i = jnp.where(free, i, row4col[j])
            return shortest, path, SR, SC, sink, i, min_val

        init = (shortest, path, SR, SC, jnp.int32(-1),
                jnp.int32(cur_row), jnp.float32(0.0))
        shortest, path, SR, SC, sink, _, min_val = jax.lax.while_loop(
            scan_cond, scan_body, init)

        # --- dual update ----------------------------------------------------
        u = u + jnp.where(
            SR,
            jnp.where(rows == cur_row,
                      min_val,
                      min_val - shortest[jnp.clip(col4row, 0, n - 1)]),
            0.0)
        v = v - jnp.where(SC, min_val - shortest, 0.0)

        # --- augment along the alternating path -----------------------------
        def aug_cond(s):
            _, _, _, done = s
            return ~done

        def aug_body(s):
            row4col, col4row, j, _ = s
            i = path[j]
            row4col = row4col.at[j].set(i)
            nxt = col4row[i]
            col4row = col4row.at[i].set(j)
            return row4col, col4row, nxt, i == cur_row

        row4col, col4row, _, _ = jax.lax.while_loop(
            aug_cond, aug_body, (row4col, col4row, sink, jnp.bool_(False)))
        return u, v, row4col, col4row

    u = jnp.zeros((n,), dtype=jnp.float32)
    v = jnp.zeros((n,), dtype=jnp.float32)
    row4col = jnp.full((n,), -1, dtype=jnp.int32)
    col4row = jnp.full((n,), -1, dtype=jnp.int32)
    u, v, row4col, col4row = jax.lax.fori_loop(
        0, n_aug, augment, (u, v, row4col, col4row))
    # rows never augmented (zero padding) stay unmatched at cost 0
    total = jnp.sum(jnp.where(col4row >= 0,
                              cost[rows, jnp.clip(col4row, 0, n - 1)],
                              0.0))
    return total, col4row, u, v


def _pad_to_square_cost(w: jnp.ndarray, nq=None, nc=None):
    """-w padded with zeros; rows/cols beyond logical (nq, nc) get cost 0."""
    n = max(w.shape)
    nq = w.shape[0] if nq is None else nq
    nc = w.shape[1] if nc is None else nc
    cost = jnp.zeros((n, n), dtype=jnp.float32)
    cost = cost.at[: w.shape[0], : w.shape[1]].set(-w.astype(jnp.float32))
    rmask = jnp.arange(n) < nq
    cmask = jnp.arange(n) < nc
    valid = rmask[:, None] & cmask[None, :]
    return jnp.where(valid, cost, 0.0)


@jax.jit
def hungarian_score(w: jnp.ndarray) -> jnp.ndarray:
    """Exact semantic overlap of one weight matrix (nq, nc)."""
    cost = _pad_to_square_cost(w)
    total, _, _, _ = _solve_square_min(cost)
    return -total


@functools.partial(jax.jit, static_argnames=())
def _hungarian_padded(w: jnp.ndarray, nq: jnp.ndarray, nc: jnp.ndarray):
    with jax.named_scope("solver"):
        cost = _pad_to_square_cost(w, nq, nc)
        # only the nq logical rows can carry weight; augmenting just
        # those is exact (see _solve_square_min) and much cheaper when
        # |Q| << |C|
        total, col4row, _, _ = _solve_square_min(cost, n_aug=nq)
    return -total, col4row


# Batched verification: vmap over (B, n, n) padded weights with logical sizes.
hungarian_batch = jax.jit(jax.vmap(_hungarian_padded, in_axes=(0, 0, 0)))
