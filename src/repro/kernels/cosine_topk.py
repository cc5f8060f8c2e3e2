"""Pallas TPU kernel: blocked cosine similarity + running top-k.

This is the token-stream generator (paper §IV): it replaces the Faiss index
probe with an MXU matmul over vocabulary tiles and an on-chip running top-k
merge, so the (|Q| x |V|) score matrix never round-trips to HBM.

Grid: one step per vocabulary tile of ``bv`` rows.  The query block and the
running top-k output blocks have constant index maps, so they stay resident
in VMEM across the sequential grid sweep (revisiting semantics); each step
computes a (nq, bv) score tile and folds it into the running (nq, k) top-k
with k max+mask selection passes.

VMEM working set per step:  nq*d (queries) + bv*d (tile) + nq*bv (scores)
+ 2*nq*k (running top-k).  With nq=256, d=256, bv=512, k=32 (f32):
256KB + 512KB + 512KB + 64KB ~= 1.3 MB — comfortably inside the ~16 MB VMEM
budget, and the matmul contraction dim d and tile dim bv are multiples of
the 128-lane MXU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30  # python scalar: jnp constants may not be closure-captured by kernels


def _kernel(qe_ref, ev_ref, vals_ref, idx_ref, *, k: int, bv: int,
            nv_real: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, _NEG, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    scores = jax.lax.dot_general(
        qe_ref[...], ev_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (nq, bv)
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    base = step * bv
    scores = jnp.where(base + cols < nv_real, scores, _NEG)
    kcols = jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 1)

    # Merge the running top-k (old) with the tile's scores (new) by k
    # max+mask passes.  Ties go to the old list, then to the lower
    # column: the first-index argmax over the concatenation [old | new].
    # Everything is a lane-wise select or a row reduction — no gather or
    # scatter, which Mosaic does not lower.
    def select(j, st):
        cv, ci, sv, ov, oi = st
        m_old = jnp.max(cv, axis=1, keepdims=True)
        a_old = jnp.argmax(cv, axis=1).astype(jnp.int32)[:, None]
        m_new = jnp.max(sv, axis=1, keepdims=True)
        a_new = jnp.argmax(sv, axis=1).astype(jnp.int32)[:, None]
        take_old = m_old >= m_new
        hit_old = kcols == a_old
        i_old = jnp.sum(jnp.where(hit_old, ci, 0), axis=1, keepdims=True)
        ov = jnp.where(kcols == j, jnp.where(take_old, m_old, m_new), ov)
        oi = jnp.where(kcols == j, jnp.where(take_old, i_old, base + a_new),
                       oi)
        cv = jnp.where(take_old & hit_old, _NEG, cv)
        sv = jnp.where(~take_old & (cols == a_new), _NEG, sv)
        return cv, ci, sv, ov, oi

    cv, ci = vals_ref[...], idx_ref[...]
    _, _, _, out_v, out_i = jax.lax.fori_loop(
        0, k, select, (cv, ci, scores, cv, ci))
    vals_ref[...] = out_v
    idx_ref[...] = out_i


@functools.partial(jax.jit,
                   static_argnames=("k", "bv", "interpret"))
def cosine_topk(qe: jnp.ndarray, ev: jnp.ndarray, k: int, bv: int = 512,
                interpret: bool = False):
    """Top-k cosine scores of each query row against all vocab rows.

    qe: (nq, d) and ev: (nv, d), both L2-normalized.  Returns
    (vals (nq, k), idx (nq, k)), descending per row.
    """
    nq, d = qe.shape
    nv, _ = ev.shape
    # pad vocab to a multiple of bv
    nv_pad = -(-nv // bv) * bv
    if nv_pad != nv:
        ev = jnp.pad(ev, ((0, nv_pad - nv), (0, 0)))
    grid = (nv_pad // bv,)
    kernel = functools.partial(_kernel, k=k, bv=bv, nv_real=nv)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nq, d), lambda i: (0, 0)),
            pl.BlockSpec((bv, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((nq, k), lambda i: (0, 0)),
            pl.BlockSpec((nq, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(qe.astype(jnp.float32), ev.astype(jnp.float32))
    return vals, idx
