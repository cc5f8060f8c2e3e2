"""Vectorized KOIOS bounds & filters (paper §III & §V, DESIGN.md §2/§8.5).

All filter state is dense per-set arrays; every bound update is a masked
vector pass over the live sets (replacing the paper's event-driven bucket
structure — see DESIGN.md §2 for why that is the TPU-correct shape).

Bounds implemented:
  * LB / iLB  — incremental greedy partial-matching score S (Lemma 5);
  * UB (arrival)  — min(|Q|,|C|) * firstsim   (Lemma 2);
  * iUB paper mode — S + min(|Q|-l, |C|-l) * s_now  (the paper's Lemma 6;
    UNSOUND, kept only for reproducing the paper's pruning-power numbers);
  * iUB sound mode — T + max(0, cap - d) * s_now  where T is the sum of the
    first-seen similarity of each distinct query element streamed with C and
    d their count (DESIGN.md §8.5 — provably >= SO);
  * theta_lb — k-th largest LB over candidate sets (Lemma 4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k",))
def kth_largest(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """k-th largest entry of x (theta computations).  k is static."""
    k = min(k, x.shape[0])
    vals = jax.lax.top_k(x, k)[0]
    return vals[k - 1]


def compute_iub(S, l, T, d, cap, s_now, seen, mode: str):
    """Current upper bound per set; +inf-ish for unseen sets (never pruned
    here — an unseen set's bound is applied on arrival)."""
    capf = cap.astype(jnp.float32)
    if mode == "paper":
        m = jnp.maximum(capf - l.astype(jnp.float32), 0.0)
        ub = S + m * s_now
    else:
        rem = jnp.maximum(capf - d.astype(jnp.float32), 0.0)
        ub = T + rem * s_now
    return jnp.where(seen, ub, jnp.float32(3.4e38))


def prune_mask(ub, theta_lb, live, lb):
    """Live sets the UB filter drops (strict <: ties survive).  A set whose
    own lower bound reaches theta_lb survives too: lb and ub are float32
    sums in different orders, so its ub can round below the lb that raised
    theta_lb.  The one rule for refinement, the wave's rounds and the host
    continuation; plain operators, so it takes jnp and numpy arrays."""
    return live & (ub < theta_lb) & (lb < theta_lb)
