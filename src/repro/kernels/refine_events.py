"""Pallas kernel: set-segmented greedy admission of one refinement chunk.

The refinement scan's admission loop is the filter phase's inner hot
path (DESIGN.md §2): per event it reads/writes a handful of per-set
state entries (S, l, T, d, seen, qmatched, qseen, slot_matched).  The
jnp serial path round-trips every one of those scalar scatters through
XLA ops over HBM-resident arrays; this kernel keeps the ENTIRE carry in
VMEM for the whole chunk and walks the chunk's lane-packed
set-segmented layout (``token_stream.pack_events_segmented``): rows are
rank *levels* — at most one event per set — so row-major admission
order is bit-identical to the serial per-event loop (cross-set events
commute), while the sequential dependency chain shrinks from one step
per event to one per level.

State gathers/scatters are dynamic scalar ref reads/writes
(``ref[pl.ds(i, 1), pl.ds(j, 1)]``) guarded by ``pl.when``.  VMEM
budget: the carry is O(num_sets * q_words + total_slots) int32/uint32
lanes — a few hundred KB at repository-partition sizes.

Interpret mode only.  Mosaic refuses the compiled kernel: a scalar
``vector.load`` at a dynamic lane offset of a VMEM ref fails with
"cannot statically prove that index in dimension 1 is a multiple of
128".  The served path runs the jnp form
(``ref.refine_events_packed_ref`` — the oracle here, bit-identical), so
:func:`refine_events` raises unless ``interpret=True``.  A compiled form
needs the carry in SMEM or a lane-vectorized admission.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scal(ref, *idx):
    """Scalar load from a 2-D ref at dynamic indices."""
    return ref[tuple(pl.ds(i, 1) for i in idx)][0, 0]


def _store(ref, val, *idx):
    ref[tuple(pl.ds(i, 1) for i in idx)] = val.reshape(1, 1)


def _refine_events_kernel(set_ref, q_ref, slot_ref, sim_ref, alive_ref,
                          s_in, l_in, t_in, d_in, seen_in, qm_in, qs_in,
                          sm_in,
                          s_out, l_out, t_out, d_out, seen_out, qm_out,
                          qs_out, sm_out, *, W: int, L: int):
    # carry copies through; the level loop then accumulates in the
    # output refs (VMEM-resident for the whole chunk)
    s_out[...] = s_in[...]
    l_out[...] = l_in[...]
    t_out[...] = t_in[...]
    d_out[...] = d_in[...]
    seen_out[...] = seen_in[...]
    qm_out[...] = qm_in[...]
    qs_out[...] = qs_in[...]
    sm_out[...] = sm_in[...]

    def lane(j, t):
        C = _scal(set_ref, t, j)

        @pl.when(C >= 0)
        def _():
            Ci = jnp.maximum(C, 0)
            do = _scal(alive_ref, 0, Ci) > 0

            @pl.when(do)
            def _():
                q = _scal(q_ref, t, j)
                slot = _scal(slot_ref, t, j)
                s = _scal(sim_ref, t, j)
                qw = q >> 5
                bit = jnp.uint32(1) << (q & 31).astype(jnp.uint32)

                # --- first-seen bookkeeping (sound iUB') ---------------
                qs_word = _scal(qs_out, Ci, qw)
                first = (qs_word & bit) == 0

                @pl.when(first)
                def _():
                    _store(t_out, _scal(t_out, 0, Ci) + s, 0, Ci)
                    _store(d_out, _scal(d_out, 0, Ci) + 1, 0, Ci)
                    _store(qs_out, qs_word | bit, Ci, qw)

                _store(seen_out, jnp.int32(1), 0, Ci)

                # --- greedy admission (iLB, Lemma 5) -------------------
                qm_word = _scal(qm_out, Ci, qw)
                adm = ((qm_word & bit) == 0) \
                    & (_scal(sm_out, 0, slot) == 0)

                @pl.when(adm)
                def _():
                    _store(s_out, _scal(s_out, 0, Ci) + s, 0, Ci)
                    _store(l_out, _scal(l_out, 0, Ci) + 1, 0, Ci)
                    _store(qm_out, qm_word | bit, Ci, qw)
                    _store(sm_out, jnp.int32(1), 0, slot)

        return t

    def level(t, _):
        jax.lax.fori_loop(0, L, lane, t)
        return 0

    jax.lax.fori_loop(0, W, level, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def refine_events(state, c_set, c_q, c_slot, c_sim,
                  interpret: bool = False):
    """Admit one lane-packed (W, L) chunk into the refinement carry.

    ``state`` is (S, l, T, d, seen, alive, qmatched, qseen,
    slot_matched) — the per-set carry minus theta, ``alive`` read-only.
    Returns the mutated fields (S, l, T, d, seen, qmatched, qseen,
    slot_matched), bit-identical to ``ref.refine_events_packed_ref``.
    Raises ``NotImplementedError`` unless ``interpret`` (see the module
    docstring).
    """
    if not interpret:
        raise NotImplementedError(
            "refine_events has no compiled form: Mosaic cannot lower its "
            "dynamic-lane scalar VMEM accesses.  Pass interpret=True, or "
            "use ref.refine_events_packed_ref (the served path's form).")
    S, l, T, d, seen, alive, qmatched, qseen, slot_matched = state
    W, L = c_set.shape
    n = S.shape[0]
    n_slots = slot_matched.shape[0]
    q_words = qmatched.shape[1]

    def spec(*shape):
        return pl.BlockSpec(shape, lambda: tuple(0 for _ in shape))

    outs = pl.pallas_call(
        functools.partial(_refine_events_kernel, W=W, L=L),
        in_specs=[spec(W, L)] * 4 + [
            spec(1, n),                       # alive
            spec(1, n), spec(1, n), spec(1, n), spec(1, n),   # S l T d
            spec(1, n),                       # seen
            spec(n, q_words), spec(n, q_words),
            spec(1, n_slots),
        ],
        out_specs=[
            spec(1, n), spec(1, n), spec(1, n), spec(1, n),
            spec(1, n),
            spec(n, q_words), spec(n, q_words),
            spec(1, n_slots),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((n, q_words), jnp.uint32),
            jax.ShapeDtypeStruct((n, q_words), jnp.uint32),
            jax.ShapeDtypeStruct((1, n_slots), jnp.int32),
        ],
        interpret=interpret,
    )(c_set, c_q, c_slot.astype(jnp.int32), c_sim,
      alive.astype(jnp.int32)[None, :],
      S[None, :], l[None, :], T[None, :], d[None, :],
      seen.astype(jnp.int32)[None, :], qmatched, qseen,
      slot_matched.astype(jnp.int32)[None, :])
    (S2, l2, T2, d2, seen2, qm2, qs2, sm2) = outs
    return (S2[0], l2[0], T2[0], d2[0], seen2[0] > 0, qm2, qs2,
            sm2[0] > 0)
