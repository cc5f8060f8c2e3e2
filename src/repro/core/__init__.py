"""KOIOS core — top-k semantic overlap set search (the paper's contribution).

Public API:
    SetCollection, SearchParams, SearchResult   (types)
    EmbeddingSimilarity, NGramJaccardSimilarity (similarity providers)
    KoiosSearch, KoiosIndex                     (search engine)
    baseline_topk, baseline_plus_topk, brute_force_topk (paper baselines)
"""
from .types import (SetCollection, SearchParams, SearchResult,
                    SearchStats, QueryValidationError, validate_query)
from .similarity import EmbeddingSimilarity, NGramJaccardSimilarity
from .inverted_index import InvertedIndex
from .token_stream import (TokenStreamCache, build_token_stream,
                           build_token_stream_batch,
                           build_token_stream_batch_cached, expand_to_events)
from .scheduler import (ExecutionPlan, SchedulerStats, run_plan,
                        run_fused_wave, run_wave)
from .search import (KoiosSearch, KoiosIndex, build_partition_indexes,
                     partition_ranges, merge_topk, merge_topk_batch)
from .baseline import baseline_topk, baseline_plus_topk, brute_force_topk

__all__ = [
    "SetCollection", "SearchParams", "SearchResult", "SearchStats",
    "QueryValidationError", "validate_query",
    "EmbeddingSimilarity", "NGramJaccardSimilarity", "InvertedIndex",
    "TokenStreamCache", "build_token_stream", "build_token_stream_batch",
    "build_token_stream_batch_cached", "expand_to_events",
    "ExecutionPlan", "SchedulerStats", "run_plan", "run_fused_wave",
    "run_wave",
    "KoiosSearch", "KoiosIndex", "build_partition_indexes",
    "partition_ranges", "merge_topk", "merge_topk_batch",
    "baseline_topk", "baseline_plus_topk", "brute_force_topk",
]
