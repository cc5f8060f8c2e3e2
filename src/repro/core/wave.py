"""On-device fused wave execution (DESIGN.md §3, fused wave program).

One device program per partition *wave* — one partition's (query x
partition) tiles for a cohort of queries — chaining what host waves
round-trip through the host (DESIGN.md §9 item 6, resolved):

  Stage A0 device-resident event expansion (DESIGN.md §3.3): the wave
           consumes the COMPACT token stream — (token, q, sim) tuples,
           uploaded with each wave — and expands it to posting-level events
           *in-trace* through the partition's device-resident CSR
           inverted index (``InvertedIndex.device_arrays``, uploaded
           once per index lifetime), a searchsorted-on-cumsum gather
           mirroring ``token_stream.expand_to_events`` bit for bit.
           This kills the per-tile host expansion and the event-array
           host->device transfer — the largest remaining per-wave
           upload (events outnumber tuples by the mean posting length);
  Stage A  all K refinement chunk scans (`lax.scan` over the shared
           (carry, chunk) -> carry step from ``core.refinement``, set-
           segmented admission with in-trace within-set ranks, vmapped
           over the wave's queries);
  Stage B  candidate compaction (:func:`compact_indices`, a stable
           sort of the survivor mask);
  Stage C  theta_lb update from the refinement bounds;
  Stage D  the first R auction/Hungarian verification rounds with
           Lemma-8 dual-bound aborts, mirroring one
           ``PostprocessState.next_request``/``apply`` cycle per round
           (top-ub batch selection, weight recompute on the normalized
           table, bracket application, UB-filter drops), with a theta
           refresh after every round.

A wave program runs on one device: its shard's, or the default one.
Shards share theta_lb between programs, not inside one: each wave starts
from the per-query bounds the host carries (the theta upload hops to the
shard's device when shards are placed), and the scheduler's
``bound_exchange`` hook all-reduces the host-side bounds over a mesh at
wave boundaries.  The host sees device data exactly once per wave, then
resumes from ``PostprocessState.from_wave`` for whatever verification
the R device rounds did not finish — host waves stay the bit-identical
oracle.

Exactness does not depend on the wave reproducing the host trajectory:
every device step only ever (a) raises certified lower bounds, (b) drops
candidates whose certified upper bound is strictly below such a bound, or
(c) records certified [lb, ub] brackets (ambiguous auction brackets are
resolved exactly on device, mirroring the pool's Hungarian fallback), so
any schedule of these steps yields the same final top-k — the invariant
that makes fused waves == host waves (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ref import event_ranks_ref
from ..runtime import instrument
from ..runtime.sharding import _round_down_f32
from .filters import prune_mask
from .matching.auction import _auction_single, make_eps_schedule
from .matching.hungarian import hungarian_batch
from .refinement import (refine_carry_init, refine_chunk_step,
                         refine_finalize)
from .similarity import cosine_row_blocks, verify_weights
from .types import SearchParams, lane_cover
from .types import pow2 as _pow2

_NEGINF = jnp.float32(-jnp.inf)


def expand_events_traced(tok, qp, sm, indptr, posting_set, posting_slot,
                         n_chunks: int, chunk: int):
    """Device-resident event expansion (DESIGN.md §3.3): one query's
    compact stream tuples -> padded event chunks, in-trace.

    The searchsorted-on-cumsum gather mirror of
    ``token_stream.expand_to_events`` + ``pad_events``, bit for bit:
    ``reps[i]`` postings per tuple, event e produced by the tuple whose
    cumulative posting count first exceeds e, posting picked by the
    within-tuple offset.  ``tok`` pads with -1 (zero postings);
    ``posting_set``/``posting_slot`` carry one trailing sentinel entry
    (-1 / 0) that every pad event's clipped gather hits, and pad sims
    repeat the final real sim (0.0 for an empty expansion) — exactly
    the host pad semantics.  Returns (set, q, slot, sim) arrays of
    shape (n_chunks, chunk).
    """
    E_pad = n_chunks * chunk
    t_pad = tok.shape[0]
    n_post = posting_set.shape[0] - 1            # trailing sentinel
    reps = jnp.where(tok >= 0, indptr[tok + 1] - indptr[tok], 0)
    ends = jnp.cumsum(reps)                      # event offset per tuple
    total = ends[-1]
    iota = jnp.arange(E_pad, dtype=jnp.int32)
    ti = jnp.minimum(jnp.searchsorted(ends, iota, side="right"), t_pad - 1)
    valid = iota < total
    tokc = jnp.maximum(tok[ti], 0)
    gather = jnp.where(valid,
                       indptr[tokc] + (iota - (ends[ti] - reps[ti])),
                       n_post)
    set_id = posting_set[gather]
    slot = posting_slot[gather]
    q = jnp.where(valid, qp[ti], 0)
    last_ti = jnp.minimum(
        jnp.searchsorted(ends, jnp.maximum(total - 1, 0), side="right"),
        t_pad - 1)
    last_sim = jnp.where(total > 0, sm[last_ti], jnp.float32(0.0))
    sim = jnp.where(valid, sm[ti], last_sim)
    return (set_id.reshape(n_chunks, chunk), q.reshape(n_chunks, chunk),
            slot.reshape(n_chunks, chunk), sim.reshape(n_chunks, chunk))


@jax.jit
def compact_indices(mask: jnp.ndarray):
    """Survivor indices of a boolean mask, ascending, -1 beyond the count
    (Stage B's candidate compaction).

    mask: (n,) bool.  Returns (idx (n,) int32, count () int32) with
    ``idx[:count]`` == ``mask.nonzero()[0]`` and ``idx[count:] == -1``.
    One stable sort puts survivors first in index order.  It is plain XLA
    and vmaps over the wave batch; a Pallas form would need an in-kernel
    prefix sum, which Mosaic does not lower.
    """
    n = mask.shape[0]
    order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
    count = jnp.sum(mask, dtype=jnp.int32)
    idx = jnp.where(jnp.arange(n, dtype=jnp.int32) < count, order,
                    jnp.int32(-1))
    return idx, count


def fused_available(params: SearchParams, sim_provider) -> bool:
    """Whether a request for fused waves runs the wave program.

    ``params.fused='off'`` never fuses.  ``'auto'`` fuses on a TPU backend
    and resolves to host waves elsewhere (callers record the resolved
    name: ``SchedulerStats.schedule``, ``RequestEngine.schedule``).
    ``'interpret'`` fuses on any backend (tests off the chip): Pallas
    kernels run in interpret mode, and the wave's auction rounds take
    their inline jnp form.  Wherever the wave is to run, a provider it
    cannot serve raises instead of quietly turning into host waves: the
    wave recomputes verification weights on device from a dense cosine
    embedding table."""
    if params.fused == "off":
        return False
    if params.fused == "auto" and jax.default_backend() != "tpu":
        return False
    if (getattr(sim_provider, "name", None) != "cosine"
            or getattr(sim_provider, "table", None) is None):
        raise ValueError(
            f"the fused wave needs a dense cosine embedding-table "
            f"provider, got {type(sim_provider).__name__}; request "
            f"SearchParams(fused='off') to serve it on host waves")
    return True


class WaveConfig(NamedTuple):
    """Static (shape/mode) parameters of one wave program — the jit key."""

    num_sets: int
    total_slots: int
    q_words: int
    k: int
    n_chunks: int
    chunk: int
    n_tuples: int                    # pow2 stream-tuple pad (Stage A0 input)
    nq_pad: int                      # query-row pad of the rounds' blocks
    #                                  (lane_cover of the cohort's max |Q|)
    c_pad: int
    B: int
    verify_batch: int
    rounds: int
    ub_mode: str
    verifier: str
    refine_layout: str
    alpha: float
    use_kernel: bool                 # auction round top-2 via the Pallas
    #                                  kernel (compiled; off in interpret
    #                                  mode, where the jnp pass is faster)
    max_rounds: int = 5000


def solver_cells(cfg: WaveConfig) -> int:
    """Cells of the blocks one wave's verification rounds solve, padding
    included: rounds x B x vb blocks, each the Hungarian's (nq_pad,
    max(nq_pad, c_pad)) rectangle or the auction's square of the larger
    side, as the host pool counts ``verify:solver_cells_padded``."""
    R, C = cfg.nq_pad, cfg.c_pad
    block = R * max(R, C) if cfg.verifier == "hungarian" else max(R, C) ** 2
    return cfg.rounds * cfg.B * min(cfg.verify_batch, cfg.num_sets) * block


def _masked_kth(x, mask, k: int):
    """k-th largest of ``x`` where ``mask``; 0.0 when fewer than k entries
    are masked in — the device mirror of ``postprocess._kth``."""
    if k > x.shape[0]:
        return jnp.float32(0.0)
    vals = jnp.where(mask, x, _NEGINF)
    kth = jax.lax.top_k(vals, k)[0][k - 1]
    return jnp.where(jnp.sum(mask) >= k, kth, jnp.float32(0.0))


# Cap on the wave's per-round verification batch.  The device rounds'
# vmapped solver runs all (B x vb) padded rows in lockstep — rows with no
# pending candidate are nq=0-cheap but still march through the batch's max
# trip count — so oversized round batches cost more than the saved host
# round-trips buy (CPU interpret A/B: 16 beats 32 by ~1.3x at the opendata
# P=4 preset).  The host continuation drains whatever the capped rounds
# leave, so the cap never affects results, only the device/host split.
_WAVE_VB_CAP = 16

# Warmup guard multipliers over the sample-observed pow2 chunk buckets:
# each observed bucket is warmed (x1 — an lru hit after the cohort sweep)
# together with the next bucket up (x2), so live streams one pow2 step
# heavier than the warmup sample still hit a compiled program.
_WAVE_CHUNK_GUARD = (1, 2)


def cohort_pads(queries: Sequence[np.ndarray], streams
                ) -> "tuple[int, int, int]":
    """(n_tuples, nq_pad, q_words) of a cohort's waves: the keys that
    ``WaveRunner.stream_operands`` builds and ``RequestEngine``'s warm-up
    compiles, from one rule.

    The stream-tuple pad and the refinement's bitmask words stay pow2.
    Only the verification rounds read the query rows, so they take the
    set-size axes' ``lane_cover``: a 514-token query solves 640-row
    blocks, not 1024-row ones (the same as pow2 for every |Q| <= 256)."""
    t_pad = _pow2(max([len(s) for s in streams] or [1]) or 1)
    nq_max = max([len(q) for q in queries] or [1])
    return (t_pad, lane_cover(max(nq_max, 1)),
            _pow2(max(1, -(-nq_max // 32))))


@functools.lru_cache(maxsize=None)
def _wave_fn(cfg: WaveConfig):
    """Build (and cache) the jitted wave program for one static config.

    The theta carry (argument 6) is donated: the program writes the
    raised bounds into the buffer it came in."""
    alpha = jnp.float32(cfg.alpha)
    vb = min(cfg.verify_batch, cfg.num_sets)

    def one_round(lb, ub, live, verified, th, qt, nq, table_n, set_tok,
                  sizes32, eps):
        """One verification round for one query — the jittable mirror of
        PostprocessState.next_request + VerifierPool.verify_requests +
        PostprocessState.apply (DESIGN.md §3)."""
        # -- filter pass (theta refresh, UB filter, No-EM, batch pick) --
        th = jnp.maximum(th, _masked_kth(lb, live, cfg.k))
        drop = prune_mask(ub, th, live, lb)
        n_drop = jnp.sum(drop & ~verified)
        live = live & ~drop
        theta_ub = _masked_kth(ub, live, cfg.k)
        no_em = live & ~verified & (lb >= theta_ub)
        need = live & ~verified & (ub > th) & ~no_em
        _, sel = jax.lax.top_k(jnp.where(need, ub, _NEGINF), vb)
        valid = jnp.take(need, sel)

        # -- weights: the host pool's weight function (bit-equal) --
        toks = set_tok[sel]
        ncs_b = jnp.where(valid, sizes32[sel], 0)
        w = verify_weights(cosine_row_blocks, table_n, qt, toks, nq,
                           sizes32[sel], alpha)
        nqs_b = jnp.where(valid, nq, 0)
        th_b = jnp.where(valid, th, _NEGINF)

        # -- solve (Lemma-8 dual aborts) --
        if cfg.verifier == "hungarian":
            so, _, _ = hungarian_batch(w, nqs_b, ncs_b)
            out_lb, out_ub = so, so
            early = jnp.zeros((vb,), bool)
            settle = valid                   # exact: every row settles
            n_early = jnp.int32(0)
            n_full = jnp.sum(valid)
        else:
            a_lb, a_ub, _, early, _ = jax.vmap(
                lambda wi, ni, ci, ti: _auction_single(
                    wi, ni, ci, eps, ti, cfg.max_rounds,
                    use_kernel=cfg.use_kernel))(w, nqs_b, ncs_b, th_b)
            # A bracket that straddles theta (or, in hybrid mode, any
            # non-degenerate bracket) is NOT settled here: its row keeps
            # the tightened bracket but stays unverified, so the host
            # continuation re-verifies it with the pool's exact fallback.
            # Paying a vmapped exact solve on-device for every row would
            # forfeit the auction's entire advantage (DESIGN.md §9 item 4)
            # in the common no-ambiguity case.
            amb = (~early) & (a_lb < th_b) & (a_ub > th_b)
            if cfg.verifier == "hybrid":
                amb = amb | ((~early) & (a_ub - a_lb > 1e-6))
            out_lb = a_lb
            out_ub = jnp.maximum(a_ub, a_lb)
            early = early & valid
            settle = valid & ~amb
            n_early = jnp.sum(early)
            n_full = jnp.sum(valid & ~early & ~amb)

        # -- apply (dense one-hot fold: no duplicate-index scatters) --
        # brackets fold in for every solved row (tightening is always
        # sound); only settled rows flip to verified
        sets_iota = jnp.arange(cfg.num_sets)
        mark = valid[:, None] & (sets_iota[None, :] == sel[:, None])
        applied = jnp.any(mark, axis=0)
        upd_lb = jnp.max(jnp.where(mark, out_lb[:, None], _NEGINF), axis=0)
        upd_ub = jnp.min(jnp.where(mark, out_ub[:, None],
                                   jnp.float32(jnp.inf)), axis=0)
        lb = jnp.where(applied, jnp.maximum(lb, upd_lb), lb)
        ub = jnp.where(applied, jnp.minimum(ub, upd_ub), ub)
        verified = verified | jnp.any(mark & settle[:, None], axis=0)
        dead = jnp.any(mark & early[:, None], axis=0)
        live = live & ~dead
        return lb, ub, live, verified, th, n_drop, n_early, n_full

    def fn(st_tok, st_q, st_sim, qtok, nqs, theta, table_n,
           set_tok, set_sizes, eps, indptr, posting_set, posting_slot):
        sizes32 = set_sizes.astype(jnp.int32)

        # ---- Stage A: K refinement chunk scans, vmapped over the wave ----
        # (each begins with Stage A0, the in-trace event expansion)
        def refine(tok, qp, sm, nq):
            chunks = expand_events_traced(
                tok, qp, sm, indptr, posting_set, posting_slot,
                cfg.n_chunks, cfg.chunk)
            if cfg.refine_layout == "segmented":
                # within-set ranks per chunk (the set-segmented layout's
                # level index), computed in-trace — lane compaction is
                # host-only (data-dependent widths), so the wave runs
                # the flat masked-level form of the same scan
                chunks = chunks + (jax.vmap(event_ranks_ref)(chunks[0]),)
            cap = jnp.minimum(sizes32, nq)
            st0 = refine_carry_init(cfg.num_sets, cfg.q_words,
                                    cfg.total_slots)
            st, killed = jax.lax.scan(
                lambda s, c: refine_chunk_step(s, c, cap, cfg.k,
                                               cfg.ub_mode,
                                               layout=cfg.refine_layout),
                st0, chunks)
            S, ub, seen, alive, th, killed_f = refine_finalize(
                st, cap, alpha, cfg.k, cfg.ub_mode)
            return S, ub, seen, alive, th, jnp.sum(killed) + killed_f

        with jax.named_scope("wave.refine"):
            S, ub0, seen, alive, th_ref, pruned_ref = jax.vmap(refine)(
                st_tok, st_q, st_sim, nqs)

        # ---- Stage B: candidate compaction ----
        with jax.named_scope("wave.compact"):
            surv = seen & alive
            surv_idx, surv_cnt = jax.vmap(compact_indices)(surv)

        # ---- Stage C: theta update ----
        theta = jnp.maximum(theta, th_ref)

        # ---- Stage D: first R verification rounds ----
        lb, ub, live = S, ub0, surv
        verified = jnp.zeros_like(surv)
        zeros = jnp.zeros((cfg.B,), jnp.int32)

        def round_step(carry, _):
            lb, ub, live, verified, theta, c_post, c_early, c_full = carry
            lb, ub, live, verified, theta, dp, de, df = jax.vmap(
                lambda l, u, lv, vf, t, q, n: one_round(
                    l, u, lv, vf, t, q, n, table_n, set_tok, sizes32, eps)
            )(lb, ub, live, verified, theta, qtok, nqs)
            return (lb, ub, live, verified, theta,
                    c_post + dp, c_early + de, c_full + df), None

        with jax.named_scope("wave.verify"):
            (lb, ub, live, verified, theta, c_post, c_early, c_full), _ = \
                jax.lax.scan(round_step,
                             (lb, ub, live, verified, theta,
                              zeros, zeros, zeros),
                             None, length=cfg.rounds)

        return (surv_idx, surv_cnt, lb, ub, live, verified,
                jnp.sum(seen, axis=1), pruned_ref,
                c_post, c_early, c_full, theta)

    return jax.jit(fn, donate_argnums=(5,))


# Engine-lifetime runner reuse (DESIGN.md §3.2): keyed by provider
# identity + the full (hashable, frozen) params.  Bounded in practice by
# the handful of provider/params combinations a process serves; entries
# hold only the eps schedule and the compiled-program cache key — ALL
# collection device state (CSR triplets, dense operands, the normalized
# table) lives on the ShardedCollection's shards and is merely borrowed
# at launch, so every runner/engine/replica over one collection shares
# one copy of everything.
_RUNNER_CACHE: dict = {}


def wave_runner_for(sim_provider, params: SearchParams) -> "WaveRunner":
    """The shared :class:`WaveRunner` of a (provider, params) pair —
    cross-request reuse of the eps schedule and compiled wave programs;
    collection operands are borrowed per-shard at launch."""
    key = (id(sim_provider), params)
    hit = _RUNNER_CACHE.get(key)
    if hit is None:
        # pin the provider so its id cannot be recycled by the allocator
        # while the cache entry is alive
        hit = _RUNNER_CACHE[key] = (WaveRunner(sim_provider, params),
                                    sim_provider)
    return hit[0]


@dataclasses.dataclass
class _TileMeta:
    """Host-side per-tile stream facts (stats; not part of the program)."""

    empty: bool
    n_tuples: int = 0
    n_events: int = 0
    n_chunks: int = 0


@dataclasses.dataclass(frozen=True)
class StreamOperands:
    """Device-resident compact stream input of one wave (§3.3): stacked
    (B_pad, T_pad) stream tuples + query tokens/lengths."""

    tok: object                      # (B_pad, T_pad) int32, -1 pad
    q_pos: object                    # (B_pad, T_pad) int32
    sim: object                      # (B_pad, T_pad) float32
    qtok: object                     # (B_pad, nq_pad) int32, -1 pad
    nqs: object                      # (B_pad,) int32
    n_tuples: int                    # T_pad (pow2)
    nq_pad: int                      # lane_cover(max |Q|), as cohort_pads
    q_words: int

    def on(self, device) -> "StreamOperands":
        """This operand set committed to ``device`` (placed-shard waves).
        ``device=None`` is the unplaced identity — the degenerate
        single-place case."""
        if device is None:
            return self
        instrument.record(f"h2d:stream_upload[{device.id}]")
        return dataclasses.replace(
            self, tok=jax.device_put(self.tok, device),
            q_pos=jax.device_put(self.q_pos, device),
            sim=jax.device_put(self.sim, device),
            qtok=jax.device_put(self.qtok, device),
            nqs=jax.device_put(self.nqs, device))


@dataclasses.dataclass
class WaveLaunch:
    """An in-flight wave: device outputs + per-tile metadata."""

    out: tuple                       # device arrays (async)
    tile_meta: List[_TileMeta]
    cfg: WaveConfig


@dataclasses.dataclass
class WaveOutputs:
    surv_idx: np.ndarray             # (B, num_sets) int32, -1 padded
    surv_cnt: np.ndarray             # (B,)
    lb: np.ndarray                   # (B, num_sets) f32
    ub: np.ndarray
    live: np.ndarray                 # (B, num_sets) bool
    verified: np.ndarray
    candidates: np.ndarray           # (B,) int32
    pruned_ref: np.ndarray
    pruned_post: np.ndarray
    em_early: np.ndarray
    em_full: np.ndarray


class WaveRunner:
    """Fused-wave context: eps schedule, compiled-program reuse, theta
    upload.  Collection device state is NOT owned here: every launch
    *borrows* the shard's CSR triplet / dense operands / normalized
    table through the :class:`~repro.runtime.collection.Shard` accessors
    — the ShardedCollection resource is the single owner, so N engines,
    replicas, and one-shot searches over one collection share one upload
    of everything (DESIGN.md §5).

    The runner holds no per-plan state — every launch threads its carry
    explicitly — so ONE runner serves every plan/request that shares a
    (provider, params) pair; obtain it via
    :func:`wave_runner_for` (the request engine and ``run_plan`` both
    do)."""

    def __init__(self, sim_provider, params: SearchParams):
        self.params = params
        self.use_kernel = not params.interpret
        self.sim = sim_provider
        self.eps = make_eps_schedule(params.auction_eps)

    def init_theta(self, theta0: np.ndarray, B_pad: int):
        t = np.zeros(B_pad, np.float32)
        t[:len(theta0)] = _round_down_f32(theta0)
        return jnp.asarray(t)

    # ------------------------------------------------------------- streams
    def stream_operands(self, queries: Sequence[np.ndarray], streams,
                        B_pad: int) -> "StreamOperands":
        """Upload one wave's input: the compact stacked stream tuples
        plus query tokens/lengths.  With the device-resident index
        expansion (§3.3) this is the wave's only host->device payload
        besides theta, replacing an event-array upload (events
        outnumber tuples by the mean posting length)."""
        t_pad, nq_pad, q_words = cohort_pads(queries, streams)
        st_tok = np.full((B_pad, t_pad), -1, np.int32)
        st_q = np.zeros((B_pad, t_pad), np.int32)
        st_sim = np.zeros((B_pad, t_pad), np.float32)
        qtok = np.full((B_pad, nq_pad), -1, np.int32)
        nqs = np.zeros(B_pad, np.int32)
        for qi, (q, s) in enumerate(zip(queries, streams)):
            st_tok[qi, :len(s)] = s.token
            st_q[qi, :len(s)] = s.q_pos
            st_sim[qi, :len(s)] = s.sim
            qtok[qi, :len(q)] = q
            nqs[qi] = len(q)
        instrument.record("h2d:stream_upload")
        return StreamOperands(
            tok=jnp.asarray(st_tok), q_pos=jnp.asarray(st_q),
            sim=jnp.asarray(st_sim), qtok=jnp.asarray(qtok),
            nqs=jnp.asarray(nqs), n_tuples=t_pad, nq_pad=nq_pad,
            q_words=q_words)

    # -------------------------------------------------------------- warmup
    def warm(self, index, B_pad: int, n_chunks: int, n_tuples: int,
             nq_pad: int, q_words: int) -> None:
        """Compile one shard-local wave config by running it on an empty
        (all-pad) cohort — the engine warmup's shard grid sweep
        (DESIGN.md §3.2): steady-state traffic whose pow2 buckets were
        warmed here reuses the compiled program, so sharded serving
        keeps the zero-recompile invariant.  Empty streams expand to
        zero events, so the run itself is cheap and touches no result
        state; already-compiled configs are lru-cache hits."""
        set_tok, sizes32, c_pad = index.wave_operands()
        indptr_dev, pset_dev, pslot_dev = index.csr_arrays()
        table_n = index.table_for(self.sim)
        put = getattr(index, "_put", jnp.asarray)
        cfg = WaveConfig(
            num_sets=index.coll.num_sets,
            total_slots=index.coll.total_tokens, q_words=q_words,
            k=self.params.k, n_chunks=n_chunks,
            chunk=self.params.chunk_size, n_tuples=n_tuples,
            nq_pad=nq_pad, c_pad=c_pad, B=B_pad,
            verify_batch=min(self.params.verify_batch, _WAVE_VB_CAP),
            rounds=self.params.wave_rounds, ub_mode=self.params.ub_mode,
            verifier=self.params.verifier,
            refine_layout=self.params.refine_layout,
            alpha=float(self.params.alpha),
            use_kernel=self.use_kernel)
        _wave_fn(cfg)(
            put(np.full((B_pad, n_tuples), -1, np.int32)),
            put(np.zeros((B_pad, n_tuples), np.int32)),
            put(np.zeros((B_pad, n_tuples), np.float32)),
            put(np.full((B_pad, nq_pad), -1, np.int32)),
            put(np.zeros(B_pad, np.int32)),
            put(np.zeros(B_pad, np.float32)),
            table_n, set_tok, sizes32, self.eps,
            indptr_dev, pset_dev, pslot_dev)

    # -------------------------------------------------------------- launch
    def launch_wave(self, index, queries: Sequence[np.ndarray], streams,
                    theta_dev) -> "tuple[WaveLaunch, object]":
        """Dispatch one partition wave; returns (launch, raised theta).

        Nothing is materialized here (JAX async dispatch).  The host
        work is uploading the cohort's stream operands and counting
        each tile's events from the host CSR counts (to size the pow2
        chunk grid); expansion itself runs in-trace from the stream
        operands and the shard's borrowed CSR arrays.

        ``index`` is a :class:`~repro.runtime.collection.Shard`: its
        CSR triplet, dense operands, and normalized table are borrowed
        views owned by the ShardedCollection.  When the shard is PLACED
        the wave runs on its device: the stream operands get a
        per-device committed copy and the theta carry hops to the
        shard's device.  Unplaced shards take the identical
        code path with every placement a no-op — the degenerate
        single-device case."""
        set_tok, sizes32, c_pad = index.wave_operands()
        indptr_dev, pset_dev, pslot_dev = index.csr_arrays()
        table_n = index.table_for(self.sim)
        coll = index.coll
        B_pad = theta_dev.shape[0]
        chunk = self.params.chunk_size
        stream_ops = self.stream_operands(queries, streams, B_pad)
        device = getattr(index, "device", None)
        if device is not None:
            stream_ops = stream_ops.on(device)
            if theta_dev.devices() != {device}:
                # the theta_lb carry hops shard-to-shard: the bound
                # raised on any earlier shard re-prunes this one
                instrument.record(f"h2d:theta_hop[s{index.sid}]")
            theta_dev = jax.device_put(theta_dev, device)

        counts = index.inv.posting_counts()
        metas: List[_TileMeta] = []
        for qi, q in enumerate(queries):
            s = streams[qi]
            n_events = int(counts[s.token].sum())
            if n_events == 0:
                metas.append(_TileMeta(empty=True))
                continue
            metas.append(_TileMeta(
                empty=False, n_tuples=len(s), n_events=n_events,
                n_chunks=_pow2(max(1, -(-n_events // chunk)))))
        n_max = max([m.n_chunks for m in metas if not m.empty] or [1])

        cfg = WaveConfig(
            num_sets=coll.num_sets, total_slots=coll.total_tokens,
            q_words=stream_ops.q_words, k=self.params.k, n_chunks=n_max,
            chunk=chunk, n_tuples=stream_ops.n_tuples,
            nq_pad=stream_ops.nq_pad, c_pad=c_pad, B=B_pad,
            verify_batch=min(self.params.verify_batch, _WAVE_VB_CAP),
            rounds=self.params.wave_rounds, ub_mode=self.params.ub_mode,
            verifier=self.params.verifier,
            refine_layout=self.params.refine_layout,
            alpha=float(self.params.alpha),
            use_kernel=self.use_kernel)
        fn = _wave_fn(cfg)
        instrument.record(f"h2d:wave_dispatch[s{getattr(index, 'sid', 0)}]")
        instrument.record("wave:solver_cells_padded", solver_cells(cfg))
        out = fn(stream_ops.tok, stream_ops.q_pos, stream_ops.sim,
                 stream_ops.qtok, stream_ops.nqs, theta_dev,
                 table_n, set_tok, sizes32, self.eps,
                 indptr_dev, pset_dev, pslot_dev)
        return WaveLaunch(out=out, tile_meta=metas, cfg=cfg), out[-1]

    # --------------------------------------------------------- materialize
    def materialize(self, launch: WaveLaunch) -> WaveOutputs:
        """One blocking device->host transfer per wave.  The trailing
        theta output is NOT read here: ``launch_wave`` returned it and
        the scheduler reads it directly."""
        instrument.record("d2h:wave_materialize")
        vals = [np.asarray(x) for x in launch.out[:-1]]
        return WaveOutputs(*vals)
