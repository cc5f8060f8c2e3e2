"""Exact maximum-weight bipartite matching in JAX (assignment problem).

Shortest-augmenting-path algorithm (Jonker–Volgenant as in Crouse 2016 /
scipy's ``linear_sum_assignment``), expressed with ``lax`` control flow so it
jits, vmaps (batched verification) and runs inside the distributed search
step.  O(nq * n^2).

Semantic-overlap conventions (paper Def. 1):
  * maximization with an *optional* one-to-one matching;
  * weights are in [0, 1] after the alpha-threshold, sub-alpha edges are 0.

We reduce to rectangular min-cost assignment on ``cost = -w`` padded with
zeros: all weights are >= 0, so padded/zero edges are exactly as good as
leaving an element unmatched, and SO == -mincost.  Padded batches
(per-element logical sizes nq/nc <= the block's rows/cols) follow the same
argument: padding rows/cols carry weight 0.  Only the logical rows that
carry some weight are augmented (a row of zeros is as good unmatched), and
a block has at least as many columns as rows, so each of them always finds
a free column.

Each augmentation's Dijkstra scan stops at the first free column it can
reach at the current shortest distance (Jonker–Volgenant's rule).  Sets
pad to many zero-cost columns, so distances tie on long plateaus; taking
the lowest-index column among ties instead walks every matched column of
the plateau first.  Under ``vmap`` every row of a batch waits for the
batch's longest scan, so the walk sets the device time: on DBLP-sized
blocks (up to 514 x 640) it was ~100 scans an augmentation, against ~1
with the rule.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INF = jnp.float32(1e30)


def _solve_rect_min(cost: jnp.ndarray, n_aug=None):
    """Min-cost assignment of the first ``n_aug`` rows of ``cost`` (R, C),
    R <= C.

    Returns (total_cost, col4row, scans).  The duals (u, v) kept along
    the way satisfy u[i] + v[j] <= cost[i, j] with equality on the
    matching; ``scans[t]`` is the number of Dijkstra iterations of row
    t's augmentation (0 for rows not augmented).

    ``n_aug`` (static or traced, <= R) limits augmentation to the first
    ``n_aug`` rows, and a row with no negative cost is not augmented
    either.  Both are exact: a row of zeros (the padding of a logical
    block, or a query token with no edge at or above alpha) extends any
    optimal matching of the other rows at zero cost, unmatched.  Each
    augmentation scans C columns, so the solve costs O(nq * R * C) at
    worst — the common verification shape has |Q| << |C|.  Under ``vmap``
    a row that is not augmented scans nothing while the batch's other
    rows run on.
    """
    R, C = cost.shape
    rows = jnp.arange(R)
    if n_aug is None:
        n_aug = R
    has_edge = jnp.any(cost < 0, axis=1)

    def augment(cur_row, carry):
        u, v, row4col, col4row, scans = carry
        active = (cur_row < n_aug) & has_edge[cur_row]

        # --- Dijkstra scan from cur_row ------------------------------------
        shortest = jnp.full((C,), _INF)
        path = jnp.full((C,), -1, dtype=jnp.int32)   # predecessor row per col
        SR = jnp.zeros((R,), dtype=bool)
        SC = jnp.zeros((C,), dtype=bool)

        def scan_cond(s):
            _, _, _, _, sink, *_ = s
            return (sink < 0) & active

        def scan_body(s):
            shortest, path, SR, SC, sink, i, min_val, n = s
            SR = SR.at[i].set(True)
            d = min_val + cost[i, :] - u[i] - v
            upd = (~SC) & (d < shortest)
            shortest = jnp.where(upd, d, shortest)
            path = jnp.where(upd, i, path)
            masked = jnp.where(SC, _INF, shortest)
            min_val = jnp.min(masked)
            # a free column at the minimum ends the scan; else the first
            at_min = masked == min_val
            free_min = at_min & (row4col < 0)
            j = jnp.where(jnp.any(free_min), jnp.argmax(free_min),
                          jnp.argmax(at_min)).astype(jnp.int32)
            SC = SC.at[j].set(True)
            free = row4col[j] < 0
            sink = jnp.where(free, j, jnp.int32(-1))
            i = jnp.where(free, i, row4col[j])
            return shortest, path, SR, SC, sink, i, min_val, n + 1

        init = (shortest, path, SR, SC, jnp.int32(-1),
                jnp.int32(cur_row), jnp.float32(0.0), jnp.int32(0))
        with jax.named_scope("solver.scan"):
            shortest, path, SR, SC, sink, _, min_val, n = \
                jax.lax.while_loop(scan_cond, scan_body, init)

        # --- dual update ----------------------------------------------------
        with jax.named_scope("solver.dual"):
            u = u + jnp.where(
                SR,
                jnp.where(rows == cur_row,
                          min_val,
                          min_val - shortest[jnp.clip(col4row, 0, C - 1)]),
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

        with jax.named_scope("solver.augment"):
            row4col, col4row, _, _ = jax.lax.while_loop(
                aug_cond, aug_body, (row4col, col4row, sink, ~active))
        scans = scans.at[cur_row].set(n)
        return u, v, row4col, col4row, scans

    u = jnp.zeros((R,), dtype=jnp.float32)
    v = jnp.zeros((C,), dtype=jnp.float32)
    row4col = jnp.full((C,), -1, dtype=jnp.int32)
    col4row = jnp.full((R,), -1, dtype=jnp.int32)
    scans = jnp.zeros((R,), dtype=jnp.int32)
    _, _, _, col4row, scans = jax.lax.fori_loop(
        0, n_aug, augment, (u, v, row4col, col4row, scans))
    # rows never augmented (zero rows) stay unmatched at cost 0
    total = jnp.sum(jnp.where(col4row >= 0,
                              cost[rows, jnp.clip(col4row, 0, C - 1)],
                              0.0))
    return total, col4row, scans


def _rect_cost(w: jnp.ndarray, nq=None, nc=None):
    """-w as an (R, max(R, C)) cost, zero outside the logical (nq, nc)
    block.  Columns widen past C only when the block has more rows than
    columns: every augmented row needs a column to fall back on."""
    R, C = w.shape
    nq = R if nq is None else nq
    nc = C if nc is None else nc
    cost = -w.astype(jnp.float32)
    if R > C:
        cost = jnp.pad(cost, ((0, 0), (0, R - C)))
    valid = (jnp.arange(R) < nq)[:, None] \
        & (jnp.arange(cost.shape[1]) < nc)[None, :]
    return jnp.where(valid, cost, 0.0)


@jax.jit
def hungarian_score(w: jnp.ndarray) -> jnp.ndarray:
    """Exact semantic overlap of one weight matrix (nq, nc)."""
    total, _, _ = _solve_rect_min(_rect_cost(w))
    return -total


def _hungarian_row(w: jnp.ndarray, nq: jnp.ndarray, nc: jnp.ndarray):
    """One padded block (R, C) with logical sizes (nq, nc): (SO, col4row,
    per-augmentation scan counts)."""
    # only the nq logical rows can carry weight; augmenting just those is
    # exact (see _solve_rect_min) and much cheaper when |Q| << |C|
    total, col4row, scans = _solve_rect_min(_rect_cost(w, nq, nc),
                                            n_aug=nq)
    return -total, col4row, scans


def _hungarian_padded(w: jnp.ndarray, nq: jnp.ndarray, nc: jnp.ndarray):
    """Exact SO of a batch of padded blocks w (B, R, C) with logical sizes
    nq, nc (B,).  Returns (SO (B,), col4row (B, R), steps): ``steps`` is
    the batch's lockstep Dijkstra iterations, the trip count of the
    vmapped scan loop (each augmentation runs as long as the batch's
    longest scan at that row)."""
    with jax.named_scope("solver"):
        so, col4row, scans = jax.vmap(_hungarian_row)(w, nq, nc)
    return so, col4row, jnp.sum(jnp.max(scans, axis=0))


# Batched verification, the host pool's and the fused wave's one exact
# solver.  Its device module keeps the name ``jit__hungarian_padded``,
# which ``bench/metrics/verify_device_ms.closed.py`` reads.
hungarian_batch = jax.jit(_hungarian_padded)
