"""Baselines of the paper §VIII-A4.

* ``brute_force_topk``  — the exact oracle: every set's SO via Hungarian
  (used by tests as ground truth on small inputs).
* ``baseline_topk``     — the paper's Baseline: token stream identifies
  candidate sets (>= one element with sim >= alpha), then every candidate
  is verified by exact graph matching (the paper parallelizes this with a
  thread pool; we batch it).
* ``baseline_plus_topk`` — Baseline+ : same, but with the iUB-filter active
  during refinement (used for WDC-scale workloads in the paper).

All reuse KOIOS' machinery with the filters disabled so that measured
speedups isolate exactly the paper's contribution.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from .postprocess import Verifier
from .refinement import run_refinement
from .search import KoiosIndex, merge_topk
from .token_stream import build_token_stream, expand_to_events
from .types import SearchParams, SearchResult, SearchStats


def _verify_all(index: KoiosIndex, query, sim_provider, ids, params,
                stats) -> SearchResult:
    verifier = Verifier(index.coll, query, sim_provider, params)
    scores = np.zeros(len(ids), np.float64)
    B = params.verify_batch
    for lo in range(0, len(ids), B):
        batch = ids[lo:lo + B]
        lb, ub, _ = verifier.verify(batch, -np.inf)
        scores[lo:lo + B] = lb
    stats.exact_matches += verifier.stats_em_full
    order = np.argsort(-scores, kind="stable")[:params.k]
    return SearchResult(
        ids=(np.asarray(ids)[order] + index.id_offset).astype(np.int32),
        lb=scores[order].astype(np.float32),
        ub=scores[order].astype(np.float32),
        stats=stats)


def baseline_topk(index: KoiosIndex, query: np.ndarray, sim_provider,
                  params: SearchParams) -> SearchResult:
    """Paper Baseline: verify every candidate set."""
    query = np.asarray(query, np.int32)
    params = dataclasses.replace(params, verifier="hungarian")
    stream = build_token_stream(query, sim_provider, params.alpha)
    events = expand_to_events(stream, index.inv)
    stats = SearchStats(stream_tuples=len(stream), stream_events=len(events))
    cand = np.unique(events.set_id)
    stats.candidates = len(cand)
    return _verify_all(index, query, sim_provider, cand, params, stats)


def baseline_plus_topk(index: KoiosIndex, query: np.ndarray, sim_provider,
                       params: SearchParams) -> SearchResult:
    """Baseline+ : iUB-filter during refinement, then verify all survivors."""
    query = np.asarray(query, np.int32)
    params = dataclasses.replace(params, verifier="hungarian")
    coll = index.coll
    stream = build_token_stream(query, sim_provider, params.alpha)
    events = expand_to_events(stream, index.inv)
    if len(events) == 0:
        return SearchResult(ids=np.zeros(0, np.int32),
                            lb=np.zeros(0, np.float32),
                            ub=np.zeros(0, np.float32), stats=SearchStats())
    ref = run_refinement(events, coll.set_sizes, len(query),
                         coll.total_tokens, params.k, params.alpha,
                         params.chunk_size, params.ub_mode)
    surv = (ref.seen & ref.alive).nonzero()[0]
    return _verify_all(index, query, sim_provider, surv, params, ref.stats)


def brute_force_topk(index: KoiosIndex, query: np.ndarray, sim_provider,
                     params: SearchParams) -> SearchResult:
    """Exact oracle over *all* sets (tests only — O(num_sets * n^3))."""
    query = np.asarray(query, np.int32)
    params = dataclasses.replace(params, verifier="hungarian")
    stats = SearchStats()
    all_ids = np.arange(index.coll.num_sets)
    return _verify_all(index, query, sim_provider, all_ids, params, stats)
