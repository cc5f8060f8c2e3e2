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

The dual update adds ``min_val - shortest[col4row[i]]`` to each row ``i``
the scan reached.  The scan records that distance as the row enters
(``ent``; inf for a row it never reached): a row other than the
augmenting one enters right after its matched column was picked, at the
``min_val`` that picked it, and a column's ``shortest`` is frozen once it
is scanned; the augmenting row enters at 0.  So the update is one
element-wise select over the rows, equal bit for bit to the gather
``shortest[col4row]``.  Under ``vmap`` that gather lowered to an
element-wise gather fusion at ~2.06 ms an augmentation on 640-row
blocks, 80% of a DBLP shard wave on a TPU v5e.

Without that gather an augmentation's device ops are many and small, so
their count, not their arithmetic, sets the loops' device time (and a
profiler trace's size: one event per op).  So the loops read the value at
a row's or column's index (``u[i]``, ``row4col[j]``, ``path[j]``,
``col4row[i]``, ``has_edge[cur_row]``) with a masked max and write one
with a select: under ``vmap`` each such read or write is a gather or
scatter op of its own, a scatter with a copy of its array first.  The
scan keeps one gather, the cost row of the row it extends.  And the first
scan iteration and the first augment step run before their loops: with
the tie rule most augmentations end in them, the loops then add only
their condition, and the first iteration fuses with the scan's initial
state instead of filling it in ops of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INF = jnp.float32(1e30)


def _pick(at, x, low):
    """``x[k]`` for the one ``k`` where ``at`` holds, as a masked max
    (every value of ``x`` is at least ``low``)."""
    return jnp.max(jnp.where(at, x, jnp.asarray(low, x.dtype)))


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
    rows, cols = jnp.arange(R), jnp.arange(C)
    if n_aug is None:
        n_aug = R
    has_edge = jnp.any(cost < 0, axis=1)

    def augment(cur_row, carry):
        u, v, row4col, col4row, scans = carry
        at_cur = rows == cur_row
        active = (cur_row < n_aug) & jnp.any(has_edge & at_cur)

        # --- Dijkstra scan from cur_row ------------------------------------
        shortest = jnp.full((C,), _INF)
        path = jnp.full((C,), -1, dtype=jnp.int32)   # predecessor row per col
        # the distance at which each row entered the scan; inf: not reached
        ent = jnp.full((R,), jnp.inf, dtype=jnp.float32)
        SC = jnp.zeros((C,), dtype=bool)

        def scan_cond(s):
            _, _, _, _, sink, *_ = s
            return (sink < 0) & active

        def scan_body(s):
            shortest, path, ent, SC, sink, i, min_val, n = s
            at_i = rows == i
            # row i enters at the distance that picked its column
            ent = jnp.where(at_i, min_val, ent)
            d = min_val + cost[i, :] - _pick(at_i, u, -jnp.inf) - v
            upd = (~SC) & (d < shortest)
            shortest = jnp.where(upd, d, shortest)
            path = jnp.where(upd, i, path)
            masked = jnp.where(SC, _INF, shortest)
            min_val = jnp.min(masked)
            # a free column at the minimum ends the scan; else the first
            # column at the minimum (argmax takes the first of the best)
            at_min = masked == min_val
            rank = jnp.where(at_min, jnp.where(row4col < 0, 2, 1), 0)
            j = jnp.argmax(rank).astype(jnp.int32)
            at_j = cols == j
            SC = SC | at_j
            r = _pick(at_j, row4col, -1)
            free = r < 0
            sink = jnp.where(free, j, jnp.int32(-1))
            i = jnp.where(free, i, r)
            return shortest, path, ent, SC, sink, i, min_val, n + 1

        init = (shortest, path, ent, SC, jnp.int32(-1),
                jnp.int32(cur_row), jnp.float32(0.0), jnp.int32(0))
        with jax.named_scope("solver.scan"):
            # the first iteration runs before the loop: most scans end in
            # it, and the loop then adds no ops
            first = jax.tree.map(lambda a, b: jnp.where(active, a, b),
                                 scan_body(init), init)
            shortest, path, ent, SC, sink, _, min_val, n = \
                jax.lax.while_loop(scan_cond, scan_body, first)

        # --- dual update ----------------------------------------------------
        with jax.named_scope("solver.dual"):
            u = u + jnp.where(ent < jnp.inf, min_val - ent, 0.0)
            v = v - jnp.where(SC, min_val - shortest, 0.0)

        # --- augment along the alternating path -----------------------------
        def aug_cond(s):
            _, _, _, done = s
            return ~done

        def aug_body(s):
            row4col, col4row, j, _ = s
            at_j = cols == j
            i = _pick(at_j, path, -1)
            at_i = rows == i
            nxt = _pick(at_i, col4row, -1)
            row4col = jnp.where(at_j, i, row4col)
            col4row = jnp.where(at_i, j, col4row)
            return row4col, col4row, nxt, i == cur_row

        with jax.named_scope("solver.augment"):
            # the first step likewise (a no-op where the row was not
            # augmented: sink is -1)
            row4col, col4row, nxt, done = aug_body(
                (row4col, col4row, sink, ~active))
            row4col, col4row, _, _ = jax.lax.while_loop(
                aug_cond, aug_body, (row4col, col4row, nxt, done | ~active))
        scans = jnp.where(at_cur, n, scans)
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
