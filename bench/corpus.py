"""The benchmark's own corpus and embedding generators.

A vectorised copy of ``src/repro/data/sets.py`` (``_sizes``,
``_generate``, ``make_embeddings``, ``sample_queries``) kept with the
benchmark, so that no later change to the program can move the data it
is measured on.  The distributions are the original's:

* set sizes: log-normal (mu = ln(0.6 avg), sigma 0.9), clipped to
  [2, max], then rescaled toward the published average and clipped
  again;
* tokens: Zipf popularity (rank ** -a) over a shuffled vocabulary,
  distinct within a set (draw 2 * size + 8 with replacement, keep the
  distinct ones in random order, redraw for the few sets still short);
* embeddings: clustered unit vectors, ``vocab / 4`` random unit centres,
  Gaussian noise of sigma sqrt((1 / 0.88 - 1) / dim), renormalised, so
  a token has a handful of neighbours at cosine >= 0.8 (the FastText
  stand-in).

The corpus comes from the configuration's fixed ``corpus_seed``: every
shard's set count and slot count are keys of the compiled wave
programs, so a corpus that changed from run to run would compile every
program afresh.  Embedding values and traffic come from ``--seed``.
"""
from __future__ import annotations

import numpy as np


def set_sizes(num_sets: int, avg: float, max_size: int,
              rng: np.random.Generator) -> np.ndarray:
    """Log-normal sizes rescaled to ``avg`` and clipped to [2, max]."""
    mu = np.log(max(avg * 0.6, 2.0))
    sizes = rng.lognormal(mu, 0.9, size=num_sets)
    sizes = np.clip(sizes, 2, max_size).astype(np.int64)
    scale = avg / max(sizes.mean(), 1.0)
    return np.clip((sizes * scale).astype(np.int64), 2, max_size)


def _distinct_draws(need: np.ndarray, cdf: np.ndarray, perm: np.ndarray,
                    rng: np.random.Generator) -> list:
    """Per set, ``need[i]`` distinct tokens drawn by popularity ``cdf``,
    in random order."""
    vocab = len(cdf)
    n = len(need)
    have = [np.zeros(0, np.int64)] * n
    todo = np.arange(n)
    first = True
    while len(todo):
        k = need[todo]
        m = np.minimum(vocab, (2 * k + 8) if first else 2 * k)
        owner = np.repeat(np.arange(len(todo)), m)
        draw = np.searchsorted(cdf, rng.random(int(m.sum())), side="right")
        draw = np.minimum(draw, vocab - 1)
        if not first:
            prev = np.concatenate([have[i] for i in todo])
            owner = np.concatenate(
                [np.repeat(np.arange(len(todo)),
                           [len(have[i]) for i in todo]), owner])
            draw = np.concatenate([prev, draw])
        # distinct (owner, token) pairs, then a random order within owner
        key = np.unique(owner * vocab + draw)
        o, t = key // vocab, key % vocab
        order = np.lexsort((rng.random(len(o)), o))
        o, t = o[order], t[order]
        starts = np.searchsorted(o, np.arange(len(todo) + 1))
        short = []
        for j, i in enumerate(todo):
            got = t[starts[j]:starts[j + 1]]
            if len(got) >= need[i]:
                have[i] = got[:need[i]]
            else:
                have[i] = got
                short.append(i)
        todo = np.asarray(short, np.int64)
        first = False
    return [perm[h] for h in have]


def make_corpus(num_sets: int, vocab: int, avg: float, max_size: int,
                zipf_a: float, seed: int):
    """(set_indptr int64, set_tokens int32) of a Table-I-matched corpus."""
    rng = np.random.default_rng(seed)
    sizes = set_sizes(num_sets, avg, max_size, rng)
    probs = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_a)
    cdf = np.cumsum(probs / probs.sum())
    perm = rng.permutation(vocab)
    sets = _distinct_draws(sizes, cdf, perm, rng)
    indptr = np.zeros(num_sets + 1, np.int64)
    np.cumsum(sizes, out=indptr[1:])
    tokens = np.concatenate(sets).astype(np.int32)
    return indptr, tokens


def make_embeddings(vocab: int, dim: int, structure_seed: int,
                    value_seed: int, cluster_size: float = 4.0,
                    intra_cos: float = 0.88) -> np.ndarray:
    """(vocab, dim) float32 clustered unit vectors (FastText stand-in).

    Which tokens share a cluster comes from ``structure_seed`` (the
    corpus seed), so the neighbourhoods, and with them the length of
    every token stream, are the same in every run; the vectors
    themselves (centres and noise) come from ``value_seed``."""
    n_clusters = max(1, int(vocab / cluster_size))
    assign = np.random.default_rng([structure_seed, 11]).integers(
        0, n_clusters, size=vocab)
    rng = np.random.default_rng([int(value_seed) & (2**63 - 1), 12])
    centers = rng.standard_normal((n_clusters, dim), dtype=np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    sigma = np.float32(np.sqrt(max(1.0 / intra_cos - 1.0, 1e-6) / dim))
    emb = centers[assign] + sigma * rng.standard_normal((vocab, dim),
                                                        dtype=np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    return emb.astype(np.float32)
