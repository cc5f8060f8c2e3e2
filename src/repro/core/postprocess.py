"""KOIOS post-processing phase (paper Alg. 2) — batched verification.

Survivors of the refinement carry bounds [lb, ub].  We repeatedly:

  1. theta_lb  = k-th largest lb (exact SO counts as lb);
  2. UB-filter: drop sets with ub <= theta_lb (cannot affect the top-k);
  3. No-EM (Lemma 7): sets with lb >= theta_ub (k-th largest ub) are in the
     answer *without* computing a matching;
  4. batch-verify the highest-ub remaining sets:  the whole batch runs
     simultaneously (vmap'd auction — the paper's thread pool becomes batch
     parallelism) with Lemma-8 dual-bound early termination at theta_lb;
     ambiguous auction brackets are re-verified exactly (Hungarian), so the
     search result is exact;
  5. stop when no unverified live set has ub > theta_lb; the answer is the
     top-k by lb.

Verification recomputes each (|Q| x |C|) weight block on the device from
the provider's table (``similarity.verify_weights``, the function the
fused wave's device rounds call too) instead of caching refinement
similarities.  The host packs token ids only; no similarity or weight
block comes back to the host, only the solver's outputs.

Multi-query serving (the batched pipeline): the loop above is factored into
a :class:`PostprocessState` state machine that *requests* verification
batches instead of running them inline.  :func:`run_postprocess_batch`
advances B queries' states in lock step and routes every round's pending
requests through one shared :class:`VerifierPool`, which pads-and-vmaps
across queries as well as candidates — fewer, fuller ``auction_batch`` /
``hungarian_batch`` calls with fewer distinct jit shapes.  Requests are
grouped by padded (|Q|, |C|) shape so each row sees exactly the trace it
would in a single-query call: ``search_batch`` results are bit-identical
to per-query ``search``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .filters import prune_mask
from .matching.auction import auction_batch, make_eps_schedule
from .matching.hungarian import hungarian_batch
from .similarity import device_weights
from .types import (SearchParams, SearchResult, SearchStats, SetCollection,
                    lane_cover, pow2)
from ..runtime import instrument
from ..runtime.instrument import span


def _record_solve(spans: dict, padded_cells: int) -> None:
    """Count one solver batch: its dispatch and output copy, its logical
    rows, and the cells of the block the solver works on (batch padding
    included; the Hungarian's (R, max(R, C)) rectangle, the auction's
    square)."""
    instrument.record("h2d:solver_dispatch")
    instrument.record("d2h:solver_materialize")
    instrument.record("verify:solver_rows",
                      sum(hi - lo for lo, hi in spans.values()))
    instrument.record("verify:solver_cells_padded", padded_cells)


def _kth(x: np.ndarray, mask: np.ndarray, kk: int) -> float:
    vals = x[mask]
    if len(vals) < kk:
        return 0.0
    return float(np.partition(vals, -kk)[-kk])


@dataclasses.dataclass
class VerifyRequest:
    """One query's pending verification batch."""

    query: np.ndarray      # (nq,) int32 query token ids
    ids: np.ndarray        # (n,) candidate set ids (partition-local)
    theta_lb: float        # Lemma-8 pruning threshold (-inf to disable)


@dataclasses.dataclass
class VerifyOutcome:
    """Per-request result brackets + matching-count accounting."""

    lb: np.ndarray         # (n,) primal score / exact SO
    ub: np.ndarray         # (n,) dual bound   / exact SO
    early: np.ndarray      # (n,) bool — certified < theta_lb (Lemma 8)
    n_full: int = 0        # full exact matchings computed
    n_early: int = 0       # matchings aborted by the dual bound


class VerifierPool:
    """Shared batched exact-SO verification across any number of queries.

    Every call packs all requests' (query, candidate-set) pairs into padded
    token-id tensors, builds their weights on the device and runs one
    solver call per distinct padded shape —
    the multi-query generalisation of the paper's verification thread pool.
    Shape grouping (|Q| and |C| padded by ``lane_cover``) keeps the jit
    cache small AND guarantees each row reproduces its single-request
    numerics exactly.
    """

    def __init__(self, coll: SetCollection, sim_provider,
                 params: SearchParams):
        self.coll = coll
        self.sim = sim_provider
        self.params = params
        self.eps_schedule = make_eps_schedule(params.auction_eps)
        # Collection-level candidate pad: every solver row is padded to
        # the cover (``lane_cover``) of the LARGEST set in the pool's
        # collection — a composition-independent constant, so (a) an
        # entry's padded shape never depends on which other requests
        # share its round (the auction is NOT bitwise padding-invariant,
        # so a composition-dependent c_pad would break search ==
        # search_batch), and (b) rounds collapse to one solver dispatch
        # per nq bucket instead of one per observed candidate-width
        # bucket — the dominant host<->device round-trip count of the
        # fused schedule's continuation (DESIGN.md §3.3).  The fused
        # wave pays the same cover for its dense operands
        # (``ShardedCollection.wave_operands``).
        self._c_pad = lane_cover(
            int(coll.set_sizes.max()) if coll.num_sets else 1, 8)
        self._alpha = np.float32(params.alpha)

    # ---------------------------------------------------- batch building
    def _candidate_tokens(self, ids: np.ndarray, B: int):
        """(B, c_pad) int32 token ids of candidate sets ``ids`` (one row
        each, -1 padding) and their (B,) logical sizes."""
        indptr = self.coll.set_indptr
        starts = indptr[ids]
        lens = (indptr[ids + 1] - starts).astype(np.int32)
        row = np.repeat(np.arange(len(ids)), lens)
        first = np.repeat(np.cumsum(lens) - lens, lens)
        col = np.arange(len(row)) - first
        c_tok = np.full((B, self._c_pad), -1, np.int32)
        c_tok[row, col] = self.coll.set_tokens[np.repeat(starts, lens) + col]
        ncs = np.zeros(B, np.int32)
        ncs[:len(ids)] = lens
        return c_tok, ncs

    def _grouped(self, requests: Sequence[VerifyRequest]):
        """Pack requests into padded solver batches, one per distinct
        (nq_pad, c_pad) shape, and launch each batch's weight program.
        Yields (w, nqs, ncs, thetas, spans) with w the (B, nq_pad, c_pad)
        device weights, nqs/ncs the (B,) logical sizes on the device,
        thetas the (B,) host thresholds and spans[i] = row range of
        request i.  Rows are independent under vmap, so batch composition
        never changes a row's result."""
        groups: dict = {}
        for i, r in enumerate(requests):
            key = lane_cover(len(r.query), 8)
            groups.setdefault(key, []).append(i)
        for nq_pad, idxs in groups.items():
            with span("koios.verify.pack"):
                rows = sum(len(requests[i].ids) for i in idxs)
                # pow2 row padding above verify_batch: cross-query rounds
                # shrink as queries finish, and an exact-fit B would
                # recompile the solver every round (single-query batches
                # stay <= verify_batch, i.e. exactly the historical shape)
                B = pow2(rows, self.params.verify_batch)
                q_tok = np.full((B, nq_pad), -1, np.int32)
                nqs = np.zeros(B, np.int32)
                thetas = np.full(B, -np.inf, np.float32)
                spans = {}
                r = 0
                for i in idxs:
                    req = requests[i]
                    n, nq = len(req.ids), len(req.query)
                    q_tok[r:r + n, :nq] = req.query
                    nqs[r:r + n] = nq
                    thetas[r:r + n] = req.theta_lb
                    spans[i] = (r, r + n)
                    r += n
                ids = np.concatenate([np.asarray(requests[i].ids, np.int64)
                                      for i in idxs])
            with span("koios.verify.weights"):
                c_tok, ncs = self._candidate_tokens(ids, B)
                nqs_d, ncs_d = jnp.asarray(nqs), jnp.asarray(ncs)
                instrument.record("verify:device_weight_rows", rows)
                instrument.record("verify:solver_cells_logical",
                                  int(nqs.astype(np.int64) @ ncs))
                w = device_weights(self.sim.row_blocks, self.sim.block_table,
                                   q_tok, c_tok, nqs_d, ncs_d, self._alpha)
            yield w, nqs_d, ncs_d, thetas, spans

    def _exact_grouped(self, requests: Sequence[VerifyRequest]
                       ) -> List[np.ndarray]:
        """Exact SO per request via shape-grouped ``hungarian_batch``."""
        out: List[Optional[np.ndarray]] = [None] * len(requests)
        for w, nqs, ncs, _thetas, spans in self._grouped(requests):
            B, R, C = w.shape
            with span("koios.verify.solve", c_pad=C):
                _record_solve(spans, B * R * max(R, C))
                so, _, steps = hungarian_batch(w, nqs, ncs)
                with span("koios.device_wait", what="solver"):
                    so, steps = jax.device_get((so, steps))
                instrument.record("verify:solver_steps", int(steps))
                for i, (lo, hi) in spans.items():
                    out[i] = so[lo:hi].copy()
        return out

    # ------------------------------------------------------------- verify
    def verify_requests(self, requests: Sequence[VerifyRequest]
                        ) -> List[VerifyOutcome]:
        """Verify all requests' candidates in (few) fused solver calls.

        Brackets are exact (lb == ub == SO) unless early-terminated, in
        which case ub < theta_lb certifies exclusion (Lemma 8).
        """
        if self.params.verifier == "hungarian":
            return [VerifyOutcome(lb=so, ub=so.copy(),
                                  early=np.zeros(len(so), bool),
                                  n_full=len(so))
                    for so in self._exact_grouped(requests)]

        outcomes: List[Optional[VerifyOutcome]] = [None] * len(requests)
        for w, nqs, ncs, thetas, spans in self._grouped(requests):
            B, R, C = w.shape
            with span("koios.verify.solve", c_pad=C):
                _record_solve(spans, B * max(R, C) ** 2)
                res = auction_batch(w, nqs, ncs, self.eps_schedule,
                                    jnp.asarray(thetas))
                with span("koios.device_wait", what="solver"):
                    lb_all = np.asarray(res.lb)
                    ub_all = np.asarray(res.ub)
                    early_all = np.asarray(res.early_stopped)
                for i, (lo, hi) in spans.items():
                    out = VerifyOutcome(lb=lb_all[lo:hi].copy(),
                                        ub=ub_all[lo:hi].copy(),
                                        early=early_all[lo:hi].copy())
                    out.n_early = int(out.early.sum())
                    out.n_full = int((~out.early).sum())
                    outcomes[i] = out

        # exact fallback for brackets that straddle theta_lb (cannot decide);
        # hybrid mode also tightens any non-degenerate bracket so downstream
        # ordering is exact
        fallback = []
        for i, (req, out) in enumerate(zip(requests, outcomes)):
            amb = (~out.early) & (out.lb < req.theta_lb) \
                & (out.ub > req.theta_lb)
            if self.params.verifier == "hybrid":
                amb |= (~out.early) & (out.ub - out.lb > 1e-6)
            if amb.any():
                fallback.append((i, amb))
        if fallback:
            sub = [VerifyRequest(requests[i].query,
                                 np.asarray(requests[i].ids)[amb],
                                 float("-inf")) for i, amb in fallback]
            for (i, amb), so in zip(fallback, self._exact_grouped(sub)):
                out = outcomes[i]
                out.lb[amb] = so
                out.ub[amb] = so
                out.n_full += int(amb.sum())
        return outcomes


class Verifier:
    """Per-query facade over :class:`VerifierPool` (baselines, single-query
    post-processing).  Keeps the historical (lb, ub, early) interface and
    stats counters."""

    def __init__(self, coll: SetCollection, query: np.ndarray, sim_provider,
                 params: SearchParams):
        self.pool = VerifierPool(coll, sim_provider, params)
        self.query = np.asarray(query, dtype=np.int32)
        self.stats_em_early = 0
        self.stats_em_full = 0

    def verify(self, ids, theta_lb: float):
        out = self.pool.verify_requests(
            [VerifyRequest(self.query, np.asarray(ids), float(theta_lb))])[0]
        self.stats_em_early += out.n_early
        self.stats_em_full += out.n_full
        return out.lb, out.ub, out.early


class PostprocessState:
    """Alg. 2 as a resumable state machine for one query.

    ``next_request()`` advances the filters until a verification batch is
    needed (returning a :class:`VerifyRequest`) or the query is finished
    (returning None); ``apply()`` folds the batch's outcome back in.  The
    request/apply cycle is exactly the inline loop of the single-query
    path, which is what lets ``run_postprocess_batch`` drive B queries in
    lock step with bit-identical per-query results.
    """

    def __init__(self, query: np.ndarray, surv_ids: np.ndarray,
                 surv_lb: np.ndarray, surv_ub: np.ndarray, theta_lb0: float,
                 params: SearchParams, stats: SearchStats,
                 id_base: int = 0):
        self.query = np.asarray(query, dtype=np.int32)
        self.params = params
        self.stats = stats
        self.id_base = int(id_base)   # request-id translation (global pool)
        self.ids = np.asarray(surv_ids)
        self.lb = np.asarray(surv_lb, np.float64).copy()
        self.ub = np.asarray(surv_ub, np.float64).copy()
        self.n = len(self.ids)
        self.live = np.ones(self.n, bool)
        self.verified = np.zeros(self.n, bool)
        self.em_early = 0
        self.em_full = 0
        self.theta_lb = max(theta_lb0, _kth(self.lb, self.live, params.k))
        self._guard = 0
        self._phase = "main"
        self._pending: Optional[np.ndarray] = None
        self._cand: Optional[np.ndarray] = None
        self._order: Optional[np.ndarray] = None

    @classmethod
    def from_wave(cls, query: np.ndarray, surv_ids: np.ndarray,
                  lb: np.ndarray, ub: np.ndarray, live: np.ndarray,
                  verified: np.ndarray, em_early: int, em_full: int,
                  theta_lb: float, params: SearchParams, stats: SearchStats,
                  id_base: int = 0) -> "PostprocessState":
        """Resume from the point a fused wave program left off.

        The wave already ran the first R verification rounds on device
        (DESIGN.md §3): ``live``/``verified`` are its masks over the
        refinement survivors, ``lb``/``ub`` its tightened brackets, and
        ``theta_lb`` the on-device-exchanged bound.  Every one of those is
        a certified bound/mask (the wave only prunes on ``ub < theta`` and
        only marks rows verified with sound brackets), so the host drive
        loop continues exactly as if it had run those rounds itself."""
        st = cls(query, surv_ids, lb, ub, float(theta_lb), params, stats,
                 id_base=id_base)
        st.live = np.asarray(live, bool).copy()
        st.verified = np.asarray(verified, bool).copy()
        st.em_early = int(em_early)
        st.em_full = int(em_full)
        return st

    def next_request(self) -> Optional[VerifyRequest]:
        k = self.params.k
        while True:
            if self._phase == "main":
                self._guard += 1
                assert self._guard < 10 * self.n + 100, \
                    "post-processing failed to converge"
                self.theta_lb = max(self.theta_lb,
                                    _kth(self.lb, self.live, k))
                # UB filter (sets that can no longer reach the top-k)
                drop = prune_mask(self.ub, self.theta_lb, self.live, self.lb)
                self.stats.pruned_postprocess += int((drop
                                                      & ~self.verified).sum())
                self.live &= ~drop
                theta_ub = _kth(self.ub, self.live, k)
                no_em = self.live & ~self.verified & (self.lb >= theta_ub)
                need = self.live & ~self.verified \
                    & (self.ub > self.theta_lb) & ~no_em
                if not need.any():
                    self.stats.pruned_no_em += int(no_em.sum())
                    self._phase = "assemble"
                    continue
                # verify the highest-ub pending sets as one batch
                nz = need.nonzero()[0]
                order = np.argsort(-self.ub[nz])
                self._pending = nz[order[:self.params.verify_batch]]
                return VerifyRequest(self.query,
                                     self.ids[self._pending] + self.id_base,
                                     float(self.theta_lb))
            if self._phase == "assemble":
                self._cand = self.live.nonzero()[0]
                order = self._cand[np.argsort(-self.lb[self._cand],
                                              kind="stable")][:k]
                if self.params.exact_scores and len(order):
                    pend = order[~self.verified[order]]
                    if len(pend):
                        self._pending = pend
                        self._phase = "exact"
                        return VerifyRequest(self.query,
                                             self.ids[pend] + self.id_base,
                                             float("-inf"))
                self._order = order
                self._phase = "done"
            if self._phase == "done":
                return None

    def raise_theta(self, theta: float) -> None:
        """Externally raise the pruning bound (cross-tile/cross-partition
        feedback from the scheduler).  Monotone and always sound: theta is
        a certified lower bound on the query's global k-th score, and the
        main loop only ever uses theta_lb to discard sets with ub below
        it.  No effect once the final ordering has been assembled."""
        self.theta_lb = max(self.theta_lb, float(theta))

    def finished(self) -> bool:
        return self._phase == "done"

    def apply(self, out: VerifyOutcome) -> None:
        idx = self._pending
        self._pending = None
        self.em_early += out.n_early
        self.em_full += out.n_full
        if self._phase == "main":
            self.lb[idx] = np.maximum(self.lb[idx], out.lb)
            self.ub[idx] = np.minimum(self.ub[idx], out.ub)
            self.verified[idx] = True
            # early-terminated sets are certified below theta_lb
            self.live[idx[out.early]] = False
        else:  # exact-scores pass over the final top-k
            assert self._phase == "exact"
            self.lb[idx] = out.lb
            self.ub[idx] = out.ub
            self.verified[idx] = True
            self._order = self._cand[np.argsort(-self.lb[self._cand],
                                                kind="stable")
                                     ][:self.params.k]
            self._phase = "done"

    def result(self) -> SearchResult:
        assert self._phase == "done", "postprocess state not drained"
        order = self._order
        self.stats.pruned_em_early += self.em_early
        self.stats.exact_matches += self.em_full
        self.stats.theta_lb_final = float(self.theta_lb)
        return SearchResult(
            ids=self.ids[order].astype(np.int32),
            lb=self.lb[order].astype(np.float32),
            ub=self.ub[order].astype(np.float32),
            stats=self.stats,
        )


def drive_states(pool: VerifierPool, states: Sequence[PostprocessState],
                 round_hook=None) -> None:
    """THE post-processing drive loop: advance any number of state
    machines in lock step over one shared verification queue.  Each round
    gathers every unfinished state's pending batch, verifies them all in
    fused solver calls, applies the outcomes, and (optionally) calls
    ``round_hook(n_active)`` — the scheduler's bound-feedback point —
    before the states emit their next requests.  Single-query
    post-processing, the batched pipeline, and the partition scheduler are
    all this loop with different state lists."""
    with span("koios.verify"):
        reqs = {i: st.next_request() for i, st in enumerate(states)}
        while True:
            active = [i for i, r in reqs.items() if r is not None]
            if not active:
                break
            outs = pool.verify_requests([reqs[i] for i in active])
            for i, out in zip(active, outs):
                states[i].apply(out)
            if round_hook is not None:
                round_hook(len(active))
            for i in active:
                reqs[i] = states[i].next_request()


def run_postprocess(coll: SetCollection, query: np.ndarray, sim_provider,
                    surv_ids: np.ndarray, surv_lb: np.ndarray,
                    surv_ub: np.ndarray, theta_lb0: float,
                    params: SearchParams,
                    stats: SearchStats) -> SearchResult:
    """Single-query post-processing — :func:`drive_states` with one state
    (compatibility wrapper)."""
    state = PostprocessState(query, surv_ids, surv_lb, surv_ub, theta_lb0,
                             params, stats)
    return run_postprocess_batch(coll, sim_provider, [state], params)[0]


def run_postprocess_batch(coll: SetCollection, sim_provider,
                          states: Sequence[PostprocessState],
                          params: SearchParams) -> List[SearchResult]:
    """B queries in lock step over one shared queue — a thin wrapper that
    owns the pool and drains the states (see :func:`drive_states`)."""
    pool = VerifierPool(coll, sim_provider, params)
    drive_states(pool, states)
    return [st.result() for st in states]
