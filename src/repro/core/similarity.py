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
  - ``row_blocks(table, q_tok, c_tok)`` over ``block_table`` -> per-row
    sim blocks, the input of :func:`verify_weights`

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


def jaccard_rows(qv: jnp.ndarray, tv: jnp.ndarray) -> jnp.ndarray:
    """(m, g) x (n, g) binary incidence rows -> (m, n) Jaccard."""
    inter = qv @ tv.T
    qa = jnp.sum(qv, axis=-1, keepdims=True)
    tb = jnp.sum(tv, axis=-1, keepdims=True)
    union = qa + tb.T - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 0.0)


_jaccard_block = jax.jit(jaccard_rows)


def cosine_row_blocks(table_n, q_tok, c_tok) -> jnp.ndarray:
    """Per-row cosine blocks over a normalized table: (B, n) non-negative
    candidate token ids against (B, m) query ids, or against one (m,)
    query every row shares (one contraction, the query rows gathered
    once) -> (B, m, n)."""
    tv = table_n[c_tok]
    if q_tok.ndim == 1:
        B, n, d = tv.shape
        s = cosine_rows(table_n[q_tok], tv.reshape(B * n, d))
        return s.reshape(-1, B, n).transpose(1, 0, 2)
    return jax.vmap(cosine_rows)(table_n[q_tok], tv)


def jaccard_row_blocks(table, q_tok, c_tok) -> jnp.ndarray:
    """Per-row n-gram Jaccard blocks over an incidence table, clipped to
    [0, 1] as :meth:`NGramJaccardSimilarity.pairwise` clips them; a (m,)
    query is shared by every row."""
    q_tok = jnp.broadcast_to(q_tok, c_tok.shape[:1] + q_tok.shape[-1:])
    return jnp.clip(jax.vmap(jaccard_rows)(table[q_tok], table[c_tok]),
                    0.0, 1.0)


def verify_weights(row_blocks, table, q_tok, c_tok, nqs, ncs, alpha
                   ) -> jnp.ndarray:
    """Alpha-thresholded verification weights, one block per row.

    The one weight construction of the system: the fused wave's device
    rounds and the host continuation's solver batches both call it.
    ``row_blocks(table, q, c)`` is the provider's per-row similarity
    block (:func:`cosine_row_blocks`, :func:`jaccard_row_blocks`).
    q_tok: (B, nq_pad) int32, or (nq_pad,) when every row verifies the
    same query (the wave's rounds); c_tok: (B, c_pad) int32; both -1
    padded.  nqs (B,) or scalar, ncs (B,): logical |Q| and |C|.  Returns
    (B, nq_pad, c_pad) float32: identical tokens fixed to 1.0 (Def. 1),
    entries below alpha zeroed, zero outside each row's logical block.
    A row's weights depend on its own tokens only, never on the rows
    beside it.
    """
    # the contraction sees at least one 128-lane tile of columns: on TPU
    # that is the layout's own padding, and on the CPU it keeps every
    # entry on the matmul kernel of a wide block, so no entry depends on
    # how narrow the collection's sets are
    c_pad = c_tok.shape[1]
    c_in = jnp.pad(jnp.maximum(c_tok, 0), ((0, 0), (0, max(0, 128 - c_pad))))
    s = row_blocks(table, jnp.maximum(q_tok, 0), c_in)[:, :, :c_pad]
    q_tok = jnp.broadcast_to(q_tok, c_tok.shape[:1] + q_tok.shape[-1:])
    same = (q_tok[:, :, None] == c_tok[:, None, :]) \
        & (q_tok >= 0)[:, :, None] & (c_tok >= 0)[:, None, :]
    s = jnp.where(same, 1.0, s)
    w = jnp.where(s >= alpha, s, 0.0)
    row_ok = jnp.arange(q_tok.shape[1])[None, :] < jnp.reshape(nqs, (-1, 1))
    col_ok = jnp.arange(c_pad)[None, :] < ncs[:, None]
    return jnp.where(row_ok[:, :, None] & col_ok[:, None, :], w, 0.0)


# the host continuation's weight program (its own module in a trace)
device_weights = jax.jit(verify_weights, static_argnums=0)


class EmbeddingSimilarity:
    """Cosine similarity over a (vocab, dim) embedding table."""

    name = "cosine"
    row_blocks = staticmethod(cosine_row_blocks)

    def __init__(self, table: np.ndarray):
        assert table.ndim == 2
        self.table = jnp.asarray(table, dtype=jnp.float32)
        self.vocab_size, self.dim = table.shape

    @property
    def block_table(self) -> jnp.ndarray:
        """The table ``row_blocks`` gathers from."""
        return self.normalized_table

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
    row_blocks = staticmethod(jaccard_row_blocks)

    def __init__(self, incidence: np.ndarray):
        assert incidence.ndim == 2
        self.table = jnp.asarray(incidence, dtype=jnp.float32)
        self.vocab_size, self.dim = incidence.shape

    @property
    def block_table(self) -> jnp.ndarray:
        """The table ``row_blocks`` gathers from."""
        return self.table

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
