"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _admit_level(st, do, l_q, l_slot, l_sim, Ci):
    """Admit one *level* of a chunk's set-segmented event layout: a lane
    vector of events that touch pairwise-distinct sets (each lane holds
    at most one event per set — the within-set rank defines the levels).

    Distinct sets make every scatter hit unique indices; padding lanes
    (``do`` False, set routed to index 0) contribute identity elements
    through commutative ops only (+0.0, +0, max False), so the fold is
    bit-identical to admitting the level's events one at a time in the
    serial per-event loop.
    """
    S, l, T, d, seen, qmatched, qseen, slot_matched = st
    qw = l_q >> 5
    bit = jnp.uint32(1) << (l_q & 31).astype(jnp.uint32)
    zero_u = jnp.uint32(0)

    # --- first-seen bookkeeping (sound iUB') ----------------------------
    first = do & ((qseen[Ci, qw] & bit) == 0)
    T = T.at[Ci].add(jnp.where(first, l_sim, 0.0))
    d = d.at[Ci].add(first.astype(jnp.int32))
    qseen = qseen.at[Ci, qw].add(jnp.where(first, bit, zero_u))
    seen = seen.at[Ci].max(do)

    # --- greedy admission (iLB, Lemma 5) --------------------------------
    q_free = (qmatched[Ci, qw] & bit) == 0
    adm = do & q_free & ~slot_matched[l_slot]
    S = S.at[Ci].add(jnp.where(adm, l_sim, 0.0))
    l = l.at[Ci].add(adm.astype(jnp.int32))
    qmatched = qmatched.at[Ci, qw].add(jnp.where(adm, bit, zero_u))
    slot_matched = slot_matched.at[l_slot].max(adm)
    return (S, l, T, d, seen, qmatched, qseen, slot_matched)


def refine_events_packed_ref(state, c_set, c_q, c_slot, c_sim):
    """Set-segmented greedy admission of one refinement chunk in the
    lane-PACKED (W, L) layout (the ``refine_events`` kernel's oracle and
    the standalone scan's production path).

    Row t holds level t of the chunk — the rank-``t`` event of every set
    that has one, compacted left into ``L`` pow2 lanes (``core.
    token_stream.pack_events_segmented``); -1 set ids pad.  Cross-set
    events commute (every mutated field is per-set and each flat slot
    belongs to exactly one set), so walking levels — ``depth`` = number
    of non-empty rows, sequential — while admitting each row as one
    L-wide vectorized scatter is bit-identical to the serial per-event
    loop (``tests/test_refinement_segmented.py``).

    state: (S, l, T, d, seen, alive, qmatched, qseen, slot_matched) —
    the per-set refinement carry minus theta (``alive`` is read-only
    here: the UB filter only runs at chunk boundaries).  Returns the
    mutated fields.
    """
    S, l, T, d, seen, alive, qmatched, qseen, slot_matched = state
    W = c_set.shape[0]
    row_live = jnp.any(c_set >= 0, axis=1)
    depth = jnp.max(jnp.where(
        row_live, jnp.arange(W, dtype=jnp.int32), -1)) + 1
    Ci_all = jnp.maximum(c_set, 0)
    # alive is chunk-constant (the UB filter runs at chunk boundaries):
    # gather it for every lane once, outside the level loop
    do_all = (c_set >= 0) & alive[Ci_all]

    def level(t, st):
        return _admit_level(st, do_all[t], c_q[t], c_slot[t], c_sim[t],
                            Ci_all[t])

    return jax.lax.fori_loop(
        0, depth, level,
        (S, l, T, d, seen, qmatched, qseen, slot_matched))


def refine_events_ref(state, c_set, c_q, c_slot, c_sim, c_rank):
    """Set-segmented admission of one chunk in the flat traced layout:
    events stay in stream order and ``c_rank`` carries each event's
    within-(chunk, set) occurrence index.  The scan walks rank levels —
    ``max rank + 1`` sequential steps — masking each level in place
    (full chunk width; the host path prefers the lane-packed form
    above, but in-trace consumers — the fused wave after device-side
    event expansion — cannot compact to data-dependent lane counts).
    Bit-identical to both the packed form and the serial loop."""
    S, l, T, d, seen, alive, qmatched, qseen, slot_matched = state
    valid = c_set >= 0
    Ci = jnp.maximum(c_set, 0)
    depth = jnp.max(jnp.where(valid, c_rank, -1)) + 1
    do_all = valid & alive[Ci]           # alive is chunk-constant

    def level(t, st):
        return _admit_level(st, do_all & (c_rank == t), c_q, c_slot,
                            c_sim, Ci)

    return jax.lax.fori_loop(
        0, depth, level,
        (S, l, T, d, seen, qmatched, qseen, slot_matched))


def event_ranks_ref(c_set: jnp.ndarray) -> jnp.ndarray:
    """Within-(chunk, set) occurrence index of each event — the traced
    mirror of ``core.token_stream.event_ranks`` for ONE chunk (the fused
    wave computes ranks in-trace after device-side event expansion).

    The stable sort keeps ties in stream order exactly like the host
    lexsort."""
    n = c_set.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(c_set, stable=True).astype(jnp.int32)
    ss = c_set[order]
    start = jnp.concatenate(
        [jnp.ones((1,), bool), ss[1:] != ss[:-1]])
    seg_start = jax.lax.cummax(jnp.where(start, iota, 0))
    rank_sorted = iota - seg_start
    return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)


def cosine_topk_ref(qe: jnp.ndarray, ev: jnp.ndarray, k: int):
    """Full-matrix cosine scores + top-k per query row.

    qe: (nq, d) L2-normalized query embeddings.
    ev: (nv, d) L2-normalized vocabulary embeddings.
    Returns (vals (nq, k), idx (nq, k)) descending.
    """
    scores = qe @ ev.T
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def auction_topk2_ref(wm: jnp.ndarray, prices: jnp.ndarray):
    """Per-row best/second-best profit and best column (one auction round's
    heavy pass).  wm: (n, m); prices: (m,).  Returns (w1, w2, jstar)."""
    profits = wm - prices[None, :]
    w1 = jnp.max(profits, axis=1)
    jstar = jnp.argmax(profits, axis=1).astype(jnp.int32)
    cols = jnp.arange(wm.shape[1])
    second = jnp.where(cols[None, :] == jstar[:, None], -jnp.inf, profits)
    w2 = jnp.max(second, axis=1)
    return w1, w2, jstar


def ssd_ref(x, dt, A, B, C, D, chunk: int = 0):
    """Mamba2 SSD (state-space duality) sequential-scan oracle.

    Shapes (single sequence):
      x:  (L, H, P)    input heads (P = head dim)
      dt: (L, H)       softplus-ed timestep per head
      A:  (H,)         negative state decay per head (A < 0)
      B:  (L, G, S)    input->state projection (G state groups, S = state dim)
      C:  (L, G, S)    state->output projection
      D:  (H,)         skip connection
    Heads are grouped: head h uses group h % G.
    Returns y: (L, H, P).

    Recurrence (per head h, group g = h % G):
      S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * B_t (outer) x_t
      y_t = C_t . S_t + D_h * x_t
    """
    L, H, P = x.shape
    G = B.shape[1]
    S = B.shape[2]

    def step(carry, t):
        st = carry                                 # (H, P, S)
        dta = jnp.exp(dt[t][:, None, None] * A[:, None, None])  # (H,1,1)
        Bg = B[t][jnp.arange(H) % G]               # (H, S)
        Cg = C[t][jnp.arange(H) % G]               # (H, S)
        upd = dt[t][:, None, None] * x[t][:, :, None] * Bg[:, None, :]
        st = dta * st + upd                        # (H, P, S)
        y = jnp.einsum("hps,hs->hp", st, Cg) + D[:, None] * x[t]
        return st, y

    st0 = jnp.zeros((H, P, S), x.dtype)
    _, ys = jax.lax.scan(step, st0, jnp.arange(L))
    return ys


def flash_attention_ref(q, k, v, causal: bool = True):
    """Dense softmax(QK^T/sqrt(d))V oracle.  q,k,v: (B,H,S,d)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
