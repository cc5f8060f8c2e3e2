"""Core datatypes for KOIOS semantic overlap search.

A :class:`SetCollection` is the repository L of the paper: a collection of
sets of tokens drawn from a shared vocabulary D.  Sets are stored in CSR
layout (``set_indptr`` / ``set_tokens``) so the whole repository is three
flat arrays — the layout every phase of the search consumes directly and
the layout that shards cleanly across a device mesh (contiguous range of
sets per shard).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def pow2(n: int, lo: int = 1) -> int:
    """Smallest power of two >= ``n`` (floored at ``lo``) — THE
    shape-bucket rounding every padded dimension shares (chunk counts,
    solver batches, wave configs, sweep rows, pairwise dispatch:
    DESIGN.md §2/§3.2).  One implementation so the bucket invariant
    tests/test_recompile.py asserts cannot diverge between stages."""
    p = lo
    while p < n:
        p *= 2
    return p


def lane_cover(n: int, lo: int = 1) -> int:
    """The pad of a set-size axis: ``pow2(n, lo)`` up to 128, the next
    multiple of 128 (one TPU lane tile) above it.  Equal to ``pow2`` for
    every n <= 256, so short-set deployments keep their shapes; a corpus
    whose largest set is a little over a power of two (DBLP's 514) pads
    to 640 instead of 1024.  The candidate cover (``VerifierPool``,
    ``Shard.wave_operands``), the host solver's row pad and the fused
    wave's query-row pad (``wave.cohort_pads``) use it; the wave's
    stream-tuple pad and refinement bitmask words stay ``pow2``."""
    if n <= 128:
        return pow2(n, lo)
    return -(-n // 128) * 128


def slot_dtype(total_slots: int):
    """Narrowest integer dtype for flat token-array slots (and posting
    ids): int32 whenever the repository fits — always at bench scales —
    halving event-transfer bytes and scatter width.  int64 repositories
    (>= 2**31 flat slots) keep the wide dtype; callers that *require*
    the narrow form (device uploads) assert via :func:`assert_int32`."""
    return np.int32 if total_slots < 2 ** 31 else np.int64


def assert_int32(n: int, what: str) -> int:
    """Guard a count that is about to be narrowed to int32 on device.
    A real exception (not ``assert``): silent wraparound here would mean
    wrong search results, and ``python -O`` must not strip the guard."""
    if n >= 2 ** 31:
        raise ValueError(
            f"{what} = {n} overflows int32 — device-resident expansion "
            f"and the int32 posting/slot layout cap at 2**31-1 entries")
    return n


def pad_ids_pow2(ids: np.ndarray, lo: int = 8) -> np.ndarray:
    """Pad an id vector to a pow2 length with id 0.  Callers slice the
    padded rows/cols off before any value is consumed, and provider ops
    are row/col-independent, so the retained values are bit-identical."""
    pad = pow2(max(len(ids), 1), lo) - len(ids)
    if pad == 0:
        return ids
    return np.concatenate([ids, np.zeros(pad, ids.dtype)])


class QueryValidationError(ValueError):
    """A query failed admission-time validation (empty, malformed, or
    backed by non-finite embedding rows) — raised/reported BEFORE any
    search work, never silently producing a garbage top-k."""


def validate_query(query, sim_provider=None) -> np.ndarray:
    """Validate one query token set at admission time; returns it as a
    contiguous int32 array.

    Structural checks: 1-D, non-empty, integer dtype, no negative ids.
    Out-of-vocabulary ids (>= vocab) are LEGAL — the identity-pair rule
    clamps an OOV token's self-similarity to 1.0, so unseen tokens are a
    supported query feature, not an error.  When ``sim_provider`` exposes
    an embedding ``table``, the IN-vocab rows the query touches are
    checked finite: a NaN/Inf embedding row would poison every similarity
    the token participates in (and through theta_lb, potentially the
    whole batch's pruning), so it is rejected here with a typed error
    instead of surfacing as a wrong result."""
    q = np.asarray(query)
    if q.ndim != 1:
        raise QueryValidationError(
            f"query must be a 1-D token array, got shape {q.shape}")
    if q.size == 0:
        raise QueryValidationError("query set is empty")
    if not np.issubdtype(q.dtype, np.integer):
        raise QueryValidationError(
            f"query tokens must be integers, got dtype {q.dtype}")
    if int(q.min()) < 0:
        raise QueryValidationError(
            f"query contains negative token id {int(q.min())}")
    table = getattr(sim_provider, "table", None)
    if table is not None:
        # per-row finiteness, computed ONCE per provider on the host and
        # cached there: a per-query device gather would compile a fresh
        # XLA executable for every distinct query length (an unbounded
        # compile stream on the admission path — each submit is O(|q|)
        # host indexing instead)
        finite = getattr(sim_provider, "_finite_rows", None)
        if finite is None:
            finite = np.isfinite(np.asarray(table)).all(axis=1)
            try:
                sim_provider._finite_rows = finite
            except AttributeError:
                pass                       # unwritable provider: recompute
        vocab = int(table.shape[0])
        in_vocab = np.unique(q[q < vocab]).astype(np.int64)
        if len(in_vocab) and not finite[in_vocab].all():
            bad = in_vocab[~finite[in_vocab]]
            raise QueryValidationError(
                f"non-finite embedding row(s) for query token(s) "
                f"{bad[:4].tolist()}")
    return np.ascontiguousarray(q, np.int32)


@dataclasses.dataclass(frozen=True)
class SetCollection:
    """Repository of sets in CSR layout.

    set i occupies ``set_tokens[set_indptr[i]:set_indptr[i+1]]``; tokens are
    vocabulary ids in ``[0, vocab_size)``.  Tokens within a set are distinct
    (sets, not bags) — enforced by the constructors in ``repro.data.sets``.
    """

    set_indptr: np.ndarray   # (num_sets + 1,) int64
    set_tokens: np.ndarray   # (total_tokens,)  int32
    vocab_size: int

    @property
    def num_sets(self) -> int:
        return len(self.set_indptr) - 1

    @property
    def total_tokens(self) -> int:
        return int(self.set_indptr[-1])

    @property
    def set_sizes(self) -> np.ndarray:
        return np.diff(self.set_indptr).astype(np.int32)

    def get_set(self, i: int) -> np.ndarray:
        return self.set_tokens[self.set_indptr[i]:self.set_indptr[i + 1]]

    def validate(self) -> None:
        assert self.set_indptr.ndim == 1 and self.set_tokens.ndim == 1
        assert self.set_indptr[0] == 0
        assert int(self.set_indptr[-1]) == len(self.set_tokens)
        assert np.all(np.diff(self.set_indptr) >= 0)
        if len(self.set_tokens):
            assert self.set_tokens.min() >= 0
            assert self.set_tokens.max() < self.vocab_size

    def slice_sets(self, lo: int, hi: int) -> "SetCollection":
        """Contiguous sub-collection [lo, hi) — used for partitioning."""
        base = self.set_indptr[lo]
        return SetCollection(
            set_indptr=(self.set_indptr[lo:hi + 1] - base).copy(),
            set_tokens=self.set_tokens[base:self.set_indptr[hi]].copy(),
            vocab_size=self.vocab_size,
        )


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Knobs of the KOIOS search (paper §VIII defaults: alpha=.8, k=10)."""

    k: int = 10
    alpha: float = 0.8
    # --- TPU adaptation knobs (DESIGN.md §2) ---
    chunk_size: int = 256          # stream tuples consumed per filter update
    verify_batch: int = 32         # candidate sets verified simultaneously
    # 'hungarian' = exact JV (paper-faithful; fastest on CPU hosts);
    # 'auction'/'hybrid' = batched auction with Lemma-8 dual early
    # termination — the TPU serving path (33x slower on a single CPU core:
    # EXPERIMENTS.md §Perf KOIOS-engine notes)
    verifier: str = "hungarian"
    auction_eps: float = 1e-4      # final epsilon of eps-scaling
    # 'sound' = corrected per-query-element iUB (DESIGN.md §8.5);
    # 'paper'  = the paper's Lemma-6 bound (unsound; reproduction mode only)
    ub_mode: str = "sound"
    # beyond-paper: stop the stream once no unseen set can enter the top-k
    early_stream_stop: bool = False
    # report exact SO for the returned top-k (extra verifications)
    exact_scores: bool = True
    # --- fused wave execution (DESIGN.md §3) ---
    # the wave kind (core.wave.fused_available): 'auto' = fused waves on
    # TPU (raising there if the wave cannot serve the provider) and
    # resolves to host waves on other backends; 'interpret' = fused waves
    # on any backend (tests off the chip), Pallas kernels in interpret
    # mode; 'off' = host waves
    fused: str = "auto"
    # device verification rounds executed inside each wave program before
    # the host drive loop takes over (R in DESIGN.md §3)
    wave_rounds: int = 2
    # generate token streams with the cosine_topk Pallas kernel instead of
    # the jnp provider sweep (interpret mode only with fused='interpret')
    stream_use_kernel: bool = False
    # refinement admission schedule (DESIGN.md §2): 'segmented' = the
    # set-segmented parallel scan (rank levels of chunk-wide vectorized
    # scatters — the default); 'serial' = the per-event reference loop.
    # Bit-identical results either way (tests/test_refinement_segmented.py)
    refine_layout: str = "segmented"

    def __post_init__(self):
        assert self.k >= 1
        assert 0.0 < self.alpha <= 1.0
        assert self.verifier in ("auction", "hungarian", "hybrid")
        assert self.ub_mode in ("sound", "paper")
        assert self.fused in ("auto", "interpret", "off")
        assert self.wave_rounds >= 0
        assert self.refine_layout in ("serial", "segmented")

    @property
    def interpret(self) -> bool:
        """Whether the search path's Pallas kernels run in interpret mode
        — only when the caller asked for it with ``fused='interpret'``."""
        return self.fused == "interpret"


@dataclasses.dataclass
class SearchStats:
    """Instrumentation mirroring the paper's Tables II/IV/V."""

    candidates: int = 0            # sets that appeared in the stream
    pruned_refinement: int = 0     # iUB/UB-filtered during refinement
    pruned_postprocess: int = 0    # UB-filtered during post-processing
    pruned_no_em: int = 0          # accepted by No-EM (no matching computed)
    pruned_em_early: int = 0       # matchings aborted by the dual bound
    exact_matches: int = 0         # full exact matchings computed
    stream_tuples: int = 0         # (q, t, sim) tuples consumed
    stream_events: int = 0         # posting-level events consumed
    refinement_chunks: int = 0
    theta_lb_final: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Top-k result: set ids, score bounds, and per-phase statistics.

    ``lb``/``ub`` bracket the true semantic overlap of each returned set;
    when ``SearchParams.exact_scores`` is set, lb == ub == SO.
    """

    ids: np.ndarray               # (k,) int32, descending score order
    lb: np.ndarray                # (k,) float32
    ub: np.ndarray                # (k,) float32
    stats: SearchStats

    @property
    def scores(self) -> np.ndarray:
        return self.lb

    def kth_score(self) -> float:
        return float(self.lb[-1]) if len(self.lb) else 0.0
