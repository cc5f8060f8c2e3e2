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

Verification recomputes the (|Q| x |C|) similarity block on the fly (MXU)
instead of caching refinement similarities — see DESIGN.md §9 item 7.

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
import jax.numpy as jnp

from .matching.auction import auction_batch, make_eps_schedule
from .matching.hungarian import hungarian_batch
from .types import (SearchParams, SearchResult, SearchStats, SetCollection,
                    pad_ids_pow2, pow2)
from ..runtime import instrument
from ..runtime.instrument import span


def _pad_pow2(n: int, lo: int = 8) -> int:
    """Solver-batch bucket rounding (shared pow2 with an 8 floor)."""
    return pow2(n, lo)


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
    weight tensors and runs one solver call per distinct padded shape —
    the multi-query generalisation of the paper's verification thread pool.
    Shape grouping (pow2-padded |Q| and |C|) keeps the jit cache small AND
    guarantees each row reproduces its single-request numerics exactly.
    """

    def __init__(self, coll: SetCollection, sim_provider,
                 params: SearchParams):
        self.coll = coll
        self.sim = sim_provider
        self.params = params
        self.eps_schedule = make_eps_schedule(params.auction_eps)
        # Collection-level candidate pad: every solver row is padded to
        # the pow2 cover of the LARGEST set in the pool's collection —
        # a composition-independent constant, so (a) an entry's padded
        # shape never depends on which other requests share its round
        # (the auction is NOT bitwise padding-invariant, so a
        # composition-dependent c_pad would break search ==
        # search_batch), and (b) rounds collapse to one solver dispatch
        # per nq bucket instead of one per observed candidate-width
        # bucket — the dominant host<->device round-trip count of the
        # fused schedule's continuation (DESIGN.md §3.3).  The fused
        # wave pays the same cover for its dense operands
        # (``wave._partition_operands``).
        self._c_pad = _pad_pow2(
            int(coll.set_sizes.max()) if coll.num_sets else 1)

    # ---------------------------------------------------------- weights
    # Cap on the candidate tokens one fused pairwise call may cover: the
    # fused matrix computes all requests' rows against all requests'
    # columns, so its waste grows with the number of requests fused —
    # chunking bounds that while typical serving batches still fuse into
    # one dispatch.
    _FUSE_TOKEN_CAP = 16384

    def weights_for_requests(self, requests: Sequence[VerifyRequest]
                             ) -> List[List[np.ndarray]]:
        """Alpha-thresholded (|Q_r|, |C_i|) weight blocks per request,
        fusing as many requests as the token cap allows per ``pairwise``
        dispatch (typically all of them)."""
        all_toks = [[self.coll.get_set(int(i)) for i in r.ids]
                    for r in requests]
        sizes = [sum(len(t) for t in ts) for ts in all_toks]
        out: List[List[np.ndarray]] = []
        lo = 0
        while lo < len(requests):
            hi, tot = lo + 1, sizes[lo]
            while hi < len(requests) and tot + sizes[hi] <= self._FUSE_TOKEN_CAP:
                tot += sizes[hi]
                hi += 1
            out.extend(self._fused_weights(requests[lo:hi],
                                           all_toks[lo:hi]))
            lo = hi
        return out

    def _fused_weights(self, requests: Sequence[VerifyRequest], toks
                       ) -> List[List[np.ndarray]]:
        """One ``pairwise`` dispatch for a run of requests.

        All queries' elements stack into the row axis and all candidate
        sets' tokens into the column axis; each request then slices its own
        (rows, per-set columns) blocks.  Every element is the same
        independent d-dim dot product as a per-set call, so the blocks are
        bit-identical to per-request (and per-set) weight computation.
        """
        assert all(ts for ts in toks), "empty verification request"
        q_cuts = np.zeros(len(requests) + 1, np.int64)
        np.cumsum([len(r.query) for r in requests], out=q_cuts[1:])
        c_cuts = np.zeros(len(requests) + 1, np.int64)
        np.cumsum([sum(len(t) for t in ts) for ts in toks], out=c_cuts[1:])
        q_cat = np.concatenate([np.asarray(r.query, np.int32)
                                for r in requests])
        c_cat = np.concatenate([t for ts in toks for t in ts])
        # pow2 row/col buckets: the fused pairwise shape is otherwise a
        # function of the round's request mix, and steady-state serving
        # (arbitrary cohort coalitions) would compile a fresh program per
        # composition.  Rows/cols of the similarity are independent
        # (row-wise normalize, per-pair dots), so pad entries change no
        # retained value — the slice drops them before use.
        # coarse floors (32 rows / 256 cols) keep the whole bucket grid
        # small enough to warm at engine startup; the extra pad work is
        # one tiny matmul block
        q_in = pad_ids_pow2(q_cat, lo=32)
        c_in = pad_ids_pow2(c_cat, lo=256)
        instrument.record("h2d:pairwise_dispatch")
        instrument.record("d2h:weights_materialize")
        s_dev = self.sim.pairwise(q_in, c_in)
        with span("koios.device_wait", what="weights"):
            s = np.asarray(s_dev)[:len(q_cat), :len(c_cat)]
        s = np.where(s >= self.params.alpha, s, 0.0).astype(np.float32)
        out = []
        for ri, ts in enumerate(toks):
            block = s[q_cuts[ri]:q_cuts[ri + 1], c_cuts[ri]:c_cuts[ri + 1]]
            cuts = np.zeros(len(ts) + 1, np.int64)
            np.cumsum([len(t) for t in ts], out=cuts[1:])
            out.append([block[:, cuts[i]:cuts[i + 1]]
                        for i in range(len(ts))])
        return out

    def weights_for(self, query: np.ndarray, ids) -> List[np.ndarray]:
        """Weight blocks of one (query, candidate batch) pair."""
        return self.weights_for_requests(
            [VerifyRequest(np.asarray(query, np.int32), np.asarray(ids),
                           float("-inf"))])[0]

    # ---------------------------------------------------- batch building
    def _grouped(self, entries):
        """Pack entries = [(mats, nq, theta), ...] into padded solver
        batches, one per distinct (nq_pad, c_pad) shape.  Yields
        (w, nqs, ncs, thetas, spans) with spans[i] = row range of entry i.
        Rows are independent under vmap, so batch composition never
        changes a row's result."""
        groups: dict = {}
        for i, (mats, nq, _theta) in enumerate(entries):
            key = (_pad_pow2(nq), self._c_pad)
            groups.setdefault(key, []).append(i)
        for (nq_pad, c_pad), idxs in groups.items():
            with span("koios.verify.pack"):
                rows = sum(len(entries[i][0]) for i in idxs)
                # pow2 row padding above verify_batch: cross-query rounds
                # shrink as queries finish, and an exact-fit B would
                # recompile the solver every round (single-query batches
                # stay <= verify_batch, i.e. exactly the historical shape)
                B = _pad_pow2(rows, self.params.verify_batch)
                w = np.zeros((B, nq_pad, c_pad), np.float32)
                nqs = np.zeros(B, np.int32)
                ncs = np.zeros(B, np.int32)
                thetas = np.full(B, -np.inf, np.float32)
                spans = {}
                r = 0
                for i in idxs:
                    mats, nq, theta = entries[i]
                    for m in mats:
                        w[r, :m.shape[0], :m.shape[1]] = m
                        nqs[r] = nq
                        ncs[r] = m.shape[1]
                        thetas[r] = theta
                        r += 1
                    spans[i] = (r - len(mats), r)
            yield w, nqs, ncs, thetas, spans

    def _exact_grouped(self, entries) -> List[np.ndarray]:
        """Exact SO per entry via shape-grouped ``hungarian_batch``."""
        out: List[Optional[np.ndarray]] = [None] * len(entries)
        for w, nqs, ncs, _thetas, spans in self._grouped(entries):
            with span("koios.verify.solve"):
                instrument.record("h2d:solver_dispatch")
                instrument.record("d2h:solver_materialize")
                so, _ = hungarian_batch(jnp.asarray(w), jnp.asarray(nqs),
                                        jnp.asarray(ncs))
                with span("koios.device_wait", what="solver"):
                    so = np.asarray(so)
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
        with span("koios.verify.weights"):
            all_mats = self.weights_for_requests(requests)
        entries = [(mats, len(r.query), float(r.theta_lb))
                   for mats, r in zip(all_mats, requests)]

        if self.params.verifier == "hungarian":
            return [VerifyOutcome(lb=so, ub=so.copy(),
                                  early=np.zeros(len(so), bool),
                                  n_full=len(so))
                    for so in self._exact_grouped(entries)]

        outcomes: List[Optional[VerifyOutcome]] = [None] * len(requests)
        for w, nqs, ncs, thetas, spans in self._grouped(entries):
            with span("koios.verify.solve"):
                instrument.record("h2d:solver_dispatch")
                instrument.record("d2h:solver_materialize")
                res = auction_batch(jnp.asarray(w), jnp.asarray(nqs),
                                    jnp.asarray(ncs), self.eps_schedule,
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
            sub = [( [entries[i][0][j] for j in amb.nonzero()[0]],
                    entries[i][1], float("-inf")) for i, amb in fallback]
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

    def weight_matrix(self, set_id: int) -> np.ndarray:
        return self.pool.weights_for(self.query, [set_id])[0]

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
                # UB filter (sets that can no longer reach the top-k;
                # strict < keeps ties, which is always safe)
                drop = self.live & (self.ub < self.theta_lb)
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
