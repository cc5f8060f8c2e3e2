"""Plain reference of KOIOS top-k semantic overlap, and the comparison
that decides ``correct``.

Semantic overlap of a query Q with a set C (the paper's Definition 2):
the weight of a maximum matching between Q and C, where an edge (q, c)
weighs the cosine of their embeddings when it is at least alpha (1.0
for identical tokens) and does not exist otherwise.  The reference
computes similarities in float64 from the benchmark's own embedding
table and solves each matching with SciPy's ``linear_sum_assignment``.
It imports nothing of the program.

Sets are visited in descending order of an upper bound (the sum over
the set's tokens of their best edge to any query token, which no
matching can exceed), and the walk stops once the bound falls below the
k-th exact score found so far: every set it skips scores below the
k-th, so the top-k is exact.

An edge whose similarity lies within ``BAND`` of alpha cannot be placed
on either side of alpha by a float32 computation, so such a set gets a
score interval: ``lo`` without those edges, ``hi`` with them.  Sets
without such an edge have ``lo == hi``.
"""
from __future__ import annotations

import heapq

import numpy as np
from scipy.optimize import linear_sum_assignment

# Width around alpha inside which float32 similarities (error ~1e-7 for
# unit vectors at 300-d) cannot decide the side of the threshold.
BAND = 2e-6


def normalize(emb: np.ndarray) -> np.ndarray:
    """Row-normalised float64 copy of an embedding table."""
    e = np.asarray(emb, np.float64)
    return e / np.maximum(np.linalg.norm(e, axis=1, keepdims=True), 1e-12)


def sims_f64(e64: np.ndarray, query: np.ndarray) -> np.ndarray:
    """(|Q|, vocab) float64 cosine of every query token with the
    vocabulary, clipped to [0, 1], identical tokens at 1.0."""
    q = np.asarray(query, np.int64)
    s = np.clip(e64[q] @ e64.T, 0.0, 1.0)
    s[np.arange(len(q)), q] = 1.0
    return s


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), returned as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1)))
    return (r & np.uint32(0xFFFF0000)).view(np.float32)


def sims_bf16(e64: np.ndarray, query: np.ndarray) -> np.ndarray:
    """The control's similarities: the same cosine with both operands
    rounded to bfloat16 and products summed in float32, one pass, as a
    default-precision float32 matmul runs on a TPU."""
    e = _bf16(e64.astype(np.float32))
    q = np.asarray(query, np.int64)
    s = np.clip((e[q] @ e.T).astype(np.float64), 0.0, 1.0)
    s[np.arange(len(q)), q] = 1.0
    return s


def _matching(w: np.ndarray) -> float:
    r, c = linear_sum_assignment(w, maximize=True)
    return float(w[r, c].sum())


class Reference:
    """Exact scores of one query against a corpus, computed lazily."""

    def __init__(self, indptr: np.ndarray, tokens: np.ndarray,
                 sims: np.ndarray, alpha: float, band: float = BAND):
        self.indptr, self.tokens = indptr, tokens
        self.alpha, self.band = alpha, band
        self.w_hi = np.where(sims >= alpha - band, sims, 0.0)
        self.ambiguous = (sims >= alpha - band) & (sims < alpha + band)
        colmax = self.w_hi.max(axis=0)
        self.ub = np.add.reduceat(colmax[tokens], indptr[:-1]) \
            if len(tokens) else np.zeros(len(indptr) - 1)
        self.scores: dict = {}           # set id -> (lo, hi)

    def score(self, sid: int) -> tuple:
        """(lo, hi) exact semantic overlap of set ``sid``."""
        got = self.scores.get(sid)
        if got is None:
            toks = self.tokens[self.indptr[sid]:self.indptr[sid + 1]]
            w = self.w_hi[:, toks]
            hi = _matching(w) if w.any() else 0.0
            lo = hi
            amb = self.ambiguous[:, toks]
            if amb.any():
                wl = np.where(amb, 0.0, w)
                lo = _matching(wl) if wl.any() else 0.0
            got = self.scores[sid] = (lo, hi)
        return got

    def topk(self, k: int) -> tuple:
        """(k-th ``lo`` rank list, k-th ``hi`` rank list): the k best
        lower and upper scores, descending, over every set that scores
        above 0.  Walks sets by upper bound until none can enter."""
        order = np.argsort(-self.ub, kind="stable")
        best: list = []                  # min-heap of the k best lo
        for sid in order:
            b = self.ub[sid]
            if b <= 0.0 or (len(best) >= k and b < best[0]):
                break
            lo, _ = self.score(int(sid))
            if len(best) < k:
                heapq.heappush(best, lo)
            elif lo > best[0]:
                heapq.heapreplace(best, lo)
        pos = [v for v in self.scores.values() if v[1] > 0.0]
        lo_rank = sorted((v[0] for v in pos), reverse=True)[:k]
        hi_rank = sorted((v[1] for v in pos), reverse=True)[:k]
        return np.asarray(lo_rank), np.asarray(hi_rank)


def _dist(s: float, lo: float, hi: float) -> float:
    return max(0.0, lo - s, s - hi)


def compare(ids, scores, ref: Reference, k: int, tol: float) -> tuple:
    """Judge one served top-k against the reference.

    Returns (gap, id_errors, messages).  ``gap`` is the widest distance
    of a served score from its reference interval, rank by rank and id
    by id.  ``id_errors`` counts served ids that cannot be in the top-k
    (no edge at or above alpha, or an upper score below the k-th lower
    score by more than ``tol``), sets clearly above the k-th score that
    were not served, and a list of the wrong length."""
    ids = [int(i) for i in ids]
    scores = [float(s) for s in scores]
    lo_rank, hi_rank = ref.topk(k)
    msgs = []
    if len(ids) != len(lo_rank):
        return (float("inf"), 1,
                [f"served {len(ids)} ids, reference has {len(lo_rank)}"])
    if not ids:
        return 0.0, 0, msgs
    gap = max(_dist(s, lo, hi) for s, lo, hi in zip(scores, lo_rank,
                                                     hi_rank))
    errors = 0
    kth_lo, kth_hi = lo_rank[-1], hi_rank[-1]
    for i, s in zip(ids, scores):
        lo, hi = ref.score(i)
        gap = max(gap, _dist(s, lo, hi))
        if hi <= 0.0:
            errors += 1
            msgs.append(f"set {i} served but has no edge >= alpha")
        elif hi < kth_lo - tol:
            errors += 1
            msgs.append(f"set {i} (reference {hi:.7f}) below the k-th "
                        f"reference score {kth_lo:.7f}")
    served = set(ids)
    for sid, (lo, _hi) in ref.scores.items():
        if lo > kth_hi + tol and sid not in served:
            errors += 1
            msgs.append(f"set {sid} (reference {lo:.7f}) above the k-th "
                        f"score {kth_hi:.7f} not served")
    if gap > tol:
        msgs.append(f"score gap {gap:.3g} over {tol:.3g}")
    return gap, errors, msgs


def control_topk(ref_sims_fn, e64, indptr, tokens, query, alpha: float,
                 k: int) -> tuple:
    """The control in the program's place: the reference's own top-k,
    with similarities from ``ref_sims_fn`` (a lower precision).  Returns
    (ids, scores) as a server would."""
    r = Reference(indptr, tokens, ref_sims_fn(e64, query), alpha, band=0.0)
    lo_rank, _ = r.topk(k)
    best = sorted(((v[0], -sid) for sid, v in r.scores.items()
                   if v[1] > 0.0), reverse=True)[:k]
    return [-s for _, s in best], [v for v, _ in best]
