"""Element-similarity providers (the paper's user-defined ``sim``).

KOIOS only requires ``sim`` to be symmetric, 1 for identical elements and in
[0, 1] otherwise (Def. 1).  The paper's experiments use cosine similarity of
FastText embeddings; its SilkMoth comparison uses Jaccard of 3-grams.  We
provide both:

* :class:`EmbeddingSimilarity` — cosine over an embedding table.  The table
  can be a frozen random-projection table (paper-faithful stand-in for
  FastText, see ``repro.data.embeddings``) or rows produced by any of the
  framework's model towers.
* :class:`NGramJaccardSimilarity` — character n-gram Jaccard, represented as
  binary n-gram incidence vectors so that the *same* blocked-matmul machinery
  drives the token stream (Jaccard(a,b) = |A∩B| / (|A|+|B|-|A∩B|), and |A∩B|
  of binary vectors is a dot product — MXU-friendly).

Both expose the interface the search engine needs:
  - ``pairwise(q_ids, t_ids)``        -> dense sim block
  - ``query_vs_vocab_block(q_ids, lo, hi)`` -> sim block against vocab slice

Identity pairs are clamped to exactly 1.0 (Def. 1) which also implements the
paper's out-of-vocabulary rule: identical tokens count with similarity one
even when their vectors are degenerate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _l2_normalize(x: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    n = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.maximum(n, eps)


def cosine_rows(qn: jnp.ndarray, tn: jnp.ndarray) -> jnp.ndarray:
    """(m, d) x (n, d) L2-normalized rows -> (m, n) cosines in [0, 1].

    The one similarity contraction of the system: the stream sweep, the
    host verifier and the fused wave's device rounds all call it, so they
    agree entry for entry.  Precision is pinned to HIGHEST because a
    default float32 matmul on TPU runs a single bf16 pass, which moves
    pairs across alpha and changes verification weights."""
    s = jax.lax.dot_general(qn, tn, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return jnp.clip(s, 0.0, 1.0)


_cosine_block = jax.jit(cosine_rows)


@jax.jit
def _jaccard_block(qv: jnp.ndarray, tv: jnp.ndarray) -> jnp.ndarray:
    inter = qv @ tv.T
    qa = jnp.sum(qv, axis=-1, keepdims=True)
    tb = jnp.sum(tv, axis=-1, keepdims=True)
    union = qa + tb.T - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)


class EmbeddingSimilarity:
    """Cosine similarity over a (vocab, dim) embedding table."""

    name = "cosine"

    def __init__(self, table: np.ndarray):
        assert table.ndim == 2
        self.table = jnp.asarray(table, dtype=jnp.float32)
        self.vocab_size, self.dim = table.shape

    @property
    def normalized_table(self) -> jnp.ndarray:
        """Row-L2-normalized table, computed once and kept device-resident
        (every similarity — stream sweep, host verifier, fused wave —
        gathers its rows from it)."""
        t = getattr(self, "_table_n", None)
        if t is None:
            t = _l2_normalize(self.table)
            self._table_n = t
        return t

    def _fix_identity(self, s: jnp.ndarray, q_ids, t_ids) -> jnp.ndarray:
        same = q_ids[:, None] == t_ids[None, :]
        return jnp.where(same, 1.0, s)

    def pairwise(self, q_ids: np.ndarray, t_ids: np.ndarray) -> jnp.ndarray:
        q_ids = jnp.asarray(q_ids)
        t_ids = jnp.asarray(t_ids)
        tn = self.normalized_table
        s = _cosine_block(tn[q_ids], tn[t_ids])
        return self._fix_identity(s, q_ids, t_ids)

    def query_vs_vocab_block(self, q_ids: np.ndarray, lo: int, hi: int) -> jnp.ndarray:
        q_ids = jnp.asarray(q_ids)
        t_ids = jnp.arange(lo, hi)
        tn = self.normalized_table
        s = _cosine_block(tn[q_ids], tn[lo:hi])
        return self._fix_identity(s, q_ids, t_ids)


def normalized_table_for(provider) -> jnp.ndarray:
    """Cached device-resident normalized table of any cosine table
    provider (the fused wave program and the kernel stream path share
    this).  :class:`EmbeddingSimilarity` subclasses expose the cached
    property directly; duck-typed providers with a ``.table`` get the
    same one-time normalize-and-cache treatment here."""
    t = getattr(provider, "normalized_table", None)
    if t is not None:
        return t
    t = getattr(provider, "_table_n", None)
    if t is None:
        from ..runtime import instrument
        instrument.record("h2d:table_upload")
        t = _l2_normalize(jnp.asarray(provider.table, jnp.float32))
        provider._table_n = t
    return t


class NGramJaccardSimilarity:
    """Jaccard of character n-grams via binary incidence vectors.

    ``incidence`` is a (vocab, n_gram_dim) {0,1} float matrix (hashed n-gram
    space).  Exact for n-gram universes up to ``n_gram_dim`` without hash
    collisions; with hashing it remains symmetric and in [0,1] (Def. 1 only
    needs those properties plus identity=1, which we clamp).
    """

    name = "ngram_jaccard"

    def __init__(self, incidence: np.ndarray):
        assert incidence.ndim == 2
        self.table = jnp.asarray(incidence, dtype=jnp.float32)
        self.vocab_size, self.dim = incidence.shape

    def _fix_identity(self, s, q_ids, t_ids):
        same = q_ids[:, None] == t_ids[None, :]
        return jnp.where(same, 1.0, jnp.clip(s, 0.0, 1.0))

    def pairwise(self, q_ids, t_ids):
        q_ids = jnp.asarray(q_ids)
        t_ids = jnp.asarray(t_ids)
        s = _jaccard_block(self.table[q_ids], self.table[t_ids])
        return self._fix_identity(s, q_ids, t_ids)

    def query_vs_vocab_block(self, q_ids, lo: int, hi: int):
        q_ids = jnp.asarray(q_ids)
        t_ids = jnp.arange(lo, hi)
        s = _jaccard_block(self.table[q_ids], self.table[lo:hi])
        return self._fix_identity(s, q_ids, t_ids)
