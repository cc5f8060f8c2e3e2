"""KOIOS refinement phase (paper Alg. 1) — chunked & vectorized.

The event stream (descending similarity, posting-level) is consumed in
fixed-size chunks.  Within a chunk, events are admitted to each set's
partial greedy matching sequentially (exactly the paper's admission order);
after each chunk all bounds are refreshed and the UB filter runs as one
masked vector pass (DESIGN.md §2).  Chunk granularity only *delays* pruning
by at most one chunk — every bound is evaluated at a valid stream position,
so the phase is exact for both ub modes' soundness guarantees.

State arrays (per set):
  S, l      — partial greedy matching score / cardinality (iLB, Lemma 5)
  T, d      — sum / count of first-seen sims per distinct query element
              (sound iUB', DESIGN.md §8.5)
  seen      — appeared in the stream (candidate set)
  alive     — not pruned
  qmatched  — (num_sets, ceil(|Q|/32)) uint32 greedy q-side occupancy
  qseen     — same layout; "query element streamed with this set"
  slot_matched — (total_tokens,) greedy t-side occupancy (flat CSR slots)

After the stream is exhausted every unstreamed pair has sim < alpha and
contributes 0 to SO, so the final bounds drop their s_now terms:
sound mode:  UB_final = T;   paper mode: UB_final = S + m*alpha.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .filters import compute_iub, kth_largest, prune_mask
from .inverted_index import InvertedIndex
from .token_stream import EventStream, pack_events_segmented, pad_events
from .types import SearchStats
from ..kernels.ref import refine_events_packed_ref, refine_events_ref
from ..runtime import instrument


@dataclasses.dataclass
class RefinementResult:
    S: np.ndarray          # (num_sets,) greedy partial score (LB)
    ub: np.ndarray         # (num_sets,) final per-set upper bound
    seen: np.ndarray       # (num_sets,) bool
    alive: np.ndarray      # (num_sets,) bool
    theta_lb: float
    stats: SearchStats


def refine_carry_init(num_sets: int, q_words: int, total_slots: int):
    """Zeroed refinement carry — the state threaded through every chunk.

    Shared by the standalone scan below and the fused wave program
    (``repro.core.wave``), which embeds the same (carry, chunk) -> carry
    step inside one device program per partition wave (DESIGN.md §3)."""
    return (
        jnp.zeros((num_sets,), jnp.float32),          # S
        jnp.zeros((num_sets,), jnp.int32),            # l
        jnp.zeros((num_sets,), jnp.float32),          # T
        jnp.zeros((num_sets,), jnp.int32),            # d
        jnp.zeros((num_sets,), bool),                 # seen
        jnp.ones((num_sets,), bool),                  # alive
        jnp.zeros((num_sets, q_words), jnp.uint32),   # qmatched
        jnp.zeros((num_sets, q_words), jnp.uint32),   # qseen
        jnp.zeros((total_slots,), bool),              # slot_matched
        jnp.float32(0.0),                             # theta_lb
    )


def refine_chunk_step(state, chunk, cap, k: int, ub_mode: str,
                      layout: str = "serial"):
    """One chunk of the refinement scan: greedy admission over the
    chunk's events, then one masked filter pass.  Returns
    (carry, n_killed); suitable for ``lax.scan`` directly and for the
    fused wave program's embedded scan.

    ``layout`` selects the admission schedule (identical bits either
    way — asserted across ub_modes x chunk sizes x partitions in
    tests/test_refinement_segmented.py):

    * ``"serial"`` — the paper's per-event loop: one sequential device
      step per event (E scalar scatters per chunk).
    * ``"segmented"`` — the set-segmented parallel scan (DESIGN.md §2):
      admission walks rank *levels* (at most one event per set each) as
      vectorized scatters, sequential only along each set's own short
      segment.  Two chunk forms are accepted: the lane-packed (W, L)
      arrays plus a trailing per-chunk ``s_now`` scalar
      (``token_stream.pack_events_segmented`` — the standalone host
      path), or flat (E,) arrays plus a trailing within-set rank vector
      (the fused wave's in-trace form after device-side event
      expansion, which cannot compact to data-dependent lane counts).
      Cross-set events commute (all mutated state is per-set and each
      flat slot belongs to one set), so only the within-set order the
      levels preserve is load-bearing.
    """
    S, l, T, d, seen, alive, qmatched, qseen, slot_matched, theta_lb = state
    if layout == "segmented":
        c_set, c_q, c_slot, c_sim, tail = chunk
        admit_state = (S, l, T, d, seen, alive, qmatched, qseen,
                       slot_matched)
        if c_set.ndim == 2:              # lane-packed (W, L) + s_now
            (S, l, T, d, seen, qmatched, qseen, slot_matched) = \
                refine_events_packed_ref(admit_state, c_set, c_q, c_slot,
                                         c_sim)
            s_now = tail
        else:                            # flat (E,) + within-set ranks
            (S, l, T, d, seen, qmatched, qseen, slot_matched) = \
                refine_events_ref(admit_state, c_set, c_q, c_slot, c_sim,
                                  tail)
            s_now = c_sim[-1]
        return _chunk_filter_pass(
            (S, l, T, d, seen, alive, qmatched, qseen, slot_matched,
             theta_lb), s_now, cap, k, ub_mode)
    assert layout == "serial", layout
    c_set, c_q, c_slot, c_sim = chunk
    chunk_len = c_set.shape[0]

    def ev_body(e, st):
        (S, l, T, d, seen, qmatched, qseen, slot_matched) = st
        C = c_set[e]
        q = c_q[e]
        slot = c_slot[e]
        s = c_sim[e]
        valid = C >= 0
        Ci = jnp.maximum(C, 0)
        do = valid & alive[Ci]
        qw = q >> 5
        qb = (q & 31).astype(jnp.uint32)
        bit = jnp.uint32(1) << qb

        # --- first-seen bookkeeping (sound iUB') ------------------------
        qs_word = qseen[Ci, qw]
        first = do & ((qs_word & bit) == 0)
        T = T.at[Ci].add(jnp.where(first, s, 0.0))
        d = d.at[Ci].add(first.astype(jnp.int32))
        qseen = qseen.at[Ci, qw].set(
            jnp.where(first, qs_word | bit, qs_word))
        seen = seen.at[Ci].set(seen[Ci] | do)

        # --- greedy admission (iLB, Lemma 5) ----------------------------
        qm_word = qmatched[Ci, qw]
        q_free = (qm_word & bit) == 0
        t_free = ~slot_matched[slot]
        adm = do & q_free & t_free
        S = S.at[Ci].add(jnp.where(adm, s, 0.0))
        l = l.at[Ci].add(adm.astype(jnp.int32))
        qmatched = qmatched.at[Ci, qw].set(
            jnp.where(adm, qm_word | bit, qm_word))
        slot_matched = slot_matched.at[slot].set(
            slot_matched[slot] | adm)
        return (S, l, T, d, seen, qmatched, qseen, slot_matched)

    (S, l, T, d, seen, qmatched, qseen, slot_matched) = jax.lax.fori_loop(
        0, chunk_len, ev_body,
        (S, l, T, d, seen, qmatched, qseen, slot_matched))
    return _chunk_filter_pass(
        (S, l, T, d, seen, alive, qmatched, qseen, slot_matched, theta_lb),
        c_sim[-1], cap, k, ub_mode)


def _chunk_filter_pass(state, s_now, cap, k: int, ub_mode: str):
    """Vectorized per-chunk filter pass (theta refresh + UB filter) —
    shared by both admission layouts; ``s_now`` is the chunk's final
    stream-order sim (a valid stream position in every layout)."""
    S, l, T, d, seen, alive, qmatched, qseen, slot_matched, theta_lb = state
    theta_lb = jnp.maximum(theta_lb, kth_largest(S, k))
    iub = compute_iub(S, l, T, d, cap, s_now, seen, ub_mode)
    killed = prune_mask(iub, theta_lb, seen & alive, S)
    alive = alive & ~killed
    n_killed = jnp.sum(killed)
    return (S, l, T, d, seen, alive, qmatched, qseen, slot_matched,
            theta_lb), n_killed


def refine_finalize(state, cap, alpha, k: int, ub_mode: str):
    """Stream exhausted: drop the s_now term (see module docstring) and run
    the final filter pass.  Returns (S, ub_final, seen, alive, theta_lb,
    n_killed_final)."""
    S, l, T, d, seen, alive, _, _, _, theta_lb = state
    s_final = alpha if ub_mode == "paper" else jnp.float32(0.0)
    ub_final = compute_iub(S, l, T, d, cap, s_final, seen, ub_mode)
    theta_lb = jnp.maximum(theta_lb, kth_largest(S, k))
    killed = prune_mask(ub_final, theta_lb, seen & alive, S)
    alive = alive & ~killed
    return S, ub_final, seen, alive, theta_lb, jnp.sum(killed)


@functools.partial(
    jax.jit,
    static_argnames=("k", "num_sets", "q_words", "total_slots", "ub_mode",
                     "layout"))
def _run_refinement(ev_set, ev_q, ev_slot, ev_sim, ev_snow, cap, k: int,
                    num_sets: int, q_words: int, total_slots: int,
                    ub_mode: str, layout: str, alpha):
    """Scan all chunks.  Serial layout: ev_* are (n_chunks, chunk) and
    ``ev_snow`` is a zero-size placeholder.  Segmented layout: ev_* are
    the lane-packed (n_chunks, W, L) arrays and ``ev_snow`` the
    per-chunk final stream-order sim (see
    ``token_stream.pack_events_segmented``)."""
    state0 = refine_carry_init(num_sets, q_words, total_slots)
    if layout == "segmented":
        chunks = (ev_set, ev_q, ev_slot, ev_sim, ev_snow)
    else:
        chunks = (ev_set, ev_q, ev_slot, ev_sim)
    state, killed_per_chunk = jax.lax.scan(
        lambda s, c: refine_chunk_step(s, c, cap, k, ub_mode,
                                       layout=layout),
        state0, chunks)
    S, ub_final, seen, alive, theta_lb, killed_final = refine_finalize(
        state, cap, alpha, k, ub_mode)
    return (S, ub_final, seen, alive, theta_lb,
            jnp.sum(killed_per_chunk) + killed_final)


def _dispatch_refinement(events: EventStream, set_sizes: np.ndarray, nq: int,
                         total_slots: int, k: int, alpha: float,
                         chunk_size: int, ub_mode: str,
                         layout: str = "segmented"):
    """Launch the jit'd refinement scan; returns (device results, n_chunks)
    without forcing the computation (JAX dispatch is async)."""
    padded = pad_events(events, chunk_size)
    n_chunks = padded[0].shape[0]
    if layout == "segmented":
        ev_set, ev_q, ev_slot, ev_sim, ev_snow = \
            pack_events_segmented(*padded)
    else:
        ev_set, ev_q, ev_slot, ev_sim = padded
        ev_snow = np.zeros(0, np.float32)
    cap = jnp.minimum(jnp.asarray(set_sizes, jnp.int32), jnp.int32(nq))
    # pow2 bitmask width: bounds jit variants to O(log |Q|) shapes
    q_words = max(1, -(-nq // 32))
    p = 1
    while p < q_words:
        p *= 2
    q_words = p
    instrument.record("h2d:refine_dispatch")
    out = _run_refinement(
        jnp.asarray(ev_set), jnp.asarray(ev_q), jnp.asarray(ev_slot),
        jnp.asarray(ev_sim), jnp.asarray(ev_snow), cap, k, len(set_sizes),
        q_words, total_slots, ub_mode, layout, jnp.float32(alpha))
    return out, n_chunks


def _materialize_refinement(out, n_chunks: int,
                            events: EventStream) -> RefinementResult:
    instrument.record("d2h:refine_materialize")
    S, ub, seen, alive, theta_lb, n_pruned = out
    stats = SearchStats(
        candidates=int(jnp.sum(seen)),
        pruned_refinement=int(n_pruned),
        stream_tuples=events.n_tuples,
        stream_events=len(events),
        refinement_chunks=n_chunks,
        theta_lb_final=float(theta_lb),
    )
    return RefinementResult(
        S=np.asarray(S), ub=np.asarray(ub), seen=np.asarray(seen),
        alive=np.asarray(alive), theta_lb=float(theta_lb), stats=stats)


def run_refinement_many(event_streams, nqs, set_sizes: np.ndarray,
                        total_slots: int, k: int, alpha: float,
                        chunk_size: int = 256,
                        ub_mode: str = "sound",
                        layout: str = "segmented"
                        ) -> "list[RefinementResult]":
    """THE refinement entry point: any number of (events, |Q|) pairs with
    pipelined dispatch.

    Each element runs the exact single-query scan (same jit, same operands
    — results are bit-identical however the list is sliced), but all scans
    are dispatched before any result is materialized, overlapping XLA
    execution with the host-side padding/dispatch of later elements.  The
    partition scheduler uses :func:`_dispatch_refinement` /
    :func:`_materialize_refinement` directly to interleave dispatch across
    partitions with different ``set_sizes``.
    """
    launched = [_dispatch_refinement(ev, set_sizes, int(nq), total_slots, k,
                                     alpha, chunk_size, ub_mode,
                                     layout=layout)
                for ev, nq in zip(event_streams, nqs)]
    return [_materialize_refinement(out, n_chunks, ev)
            for (out, n_chunks), ev in zip(launched, event_streams)]


def run_refinement(events: EventStream, set_sizes: np.ndarray, nq: int,
                   total_slots: int, k: int, alpha: float,
                   chunk_size: int = 256,
                   ub_mode: str = "sound",
                   layout: str = "segmented") -> RefinementResult:
    """Single-stream refinement (compatibility wrapper)."""
    return run_refinement_many([events], [nq], set_sizes, total_slots, k,
                               alpha, chunk_size, ub_mode, layout=layout)[0]


def run_refinement_batch(event_streams, queries, set_sizes: np.ndarray,
                         total_slots: int, k: int, alpha: float,
                         chunk_size: int = 256,
                         ub_mode: str = "sound") -> "list[RefinementResult]":
    """B-query refinement (compatibility wrapper over
    :func:`run_refinement_many`)."""
    return run_refinement_many(event_streams, [len(q) for q in queries],
                               set_sizes, total_slots, k, alpha, chunk_size,
                               ub_mode)
