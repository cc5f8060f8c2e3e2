"""The verifier's weight blocks are built on the device
(``similarity.verify_weights``): equal, entry for entry, to the host
construction they replace (one all-pairs ``pairwise`` block, thresholded
at alpha, sliced per candidate set, packed), independent of the rows
beside them, and verified to the exact semantic overlap, for the cosine
and the n-gram Jaccard providers."""
import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

from repro.core import (EmbeddingSimilarity, KoiosSearch,
                        NGramJaccardSimilarity, SearchParams)
from repro.core.postprocess import VerifierPool, VerifyRequest
from repro.core.types import pad_ids_pow2
from repro.data import make_collection, make_embeddings, sample_queries

PROVIDERS = ["cosine", "ngram"]
VERIFIERS = ["hungarian", "auction", "hybrid"]


@pytest.fixture(scope="module")
def coll():
    return make_collection(num_sets=60, vocab_size=300, avg_size=8,
                           max_size=20, zipf_a=1.1, seed=3)


def _provider(name, vocab):
    if name == "cosine":
        return EmbeddingSimilarity(make_embeddings(vocab, dim=16,
                                                   cluster_size=4.0, seed=3))
    rng = np.random.default_rng(3)
    return NGramJaccardSimilarity(
        (rng.random((vocab, 48)) > 0.75).astype(np.float32))


def _requests(coll, rng, n_req):
    """Requests whose queries share tokens with their candidates (identity
    pairs) and whose query lengths span two nq buckets."""
    out = []
    for _ in range(n_req):
        ids = rng.choice(coll.num_sets, size=int(rng.integers(1, 9)),
                         replace=False)
        own = np.concatenate([coll.get_set(int(i))[:2] for i in ids])
        extra = rng.choice(coll.vocab_size, size=int(rng.integers(1, 12)))
        query = np.unique(np.concatenate([own, extra])).astype(np.int32)
        out.append(VerifyRequest(query, ids, float("-inf")))
    return out


def _host_blocks(coll, sim, requests, alpha):
    """The construction the device program replaces: one ``pairwise``
    block over all requests' rows and columns, thresholded at alpha on the
    host, then sliced into one (|Q|, |C|) block per candidate set."""
    q_cat = np.concatenate([r.query for r in requests])
    toks = [[coll.get_set(int(i)) for i in r.ids] for r in requests]
    c_cat = np.concatenate([t for ts in toks for t in ts])
    s = np.asarray(sim.pairwise(pad_ids_pow2(q_cat, lo=32),
                                pad_ids_pow2(c_cat, lo=256)))
    s = s[:len(q_cat), :len(c_cat)]
    w = np.where(s >= alpha, s, 0.0).astype(np.float32)
    blocks, qo, co = [], 0, 0
    for r, ts in zip(requests, toks):
        row = []
        for t in ts:
            row.append(w[qo:qo + len(r.query), co:co + len(t)])
            co += len(t)
        qo += len(r.query)
        blocks.append(row)
    return blocks, s


def _alpha_at_a_pair(sim, requests, coll):
    """An alpha equal to one of the blocks' own similarities, so some
    pairs sit exactly at the threshold."""
    blocks, _ = _host_blocks(coll, sim, requests, 0.0)
    vals = np.unique(np.concatenate([b.ravel() for row in blocks
                                     for b in row]))
    vals = vals[(vals > 0.05) & (vals < 1.0)]
    return float(vals[len(vals) // 2])


def _device_rows(pool, requests):
    """Request i's per-set weight rows, as the pool packs and builds them,
    plus every packed batch (to check the padding)."""
    rows, batches = {}, []
    for w, nqs, ncs, _thetas, spans in pool._grouped(requests):
        w = np.asarray(w)
        batches.append((w, np.asarray(nqs), np.asarray(ncs), spans))
        for i, (lo, hi) in spans.items():
            rows[i] = w[lo:hi]
    return rows, batches


@pytest.mark.parametrize("provider", PROVIDERS)
def test_device_weights_equal_the_host_construction(coll, provider):
    sim = _provider(provider, coll.vocab_size)
    requests = _requests(coll, np.random.default_rng(5), 9)
    alpha = _alpha_at_a_pair(sim, requests, coll)
    pool = VerifierPool(coll, sim, SearchParams(alpha=alpha,
                                                verify_batch=8))
    host, _ = _host_blocks(coll, sim, requests, np.float32(alpha))
    rows, batches = _device_rows(pool, requests)
    n_identity = n_at_alpha = 0
    for i, r in enumerate(requests):
        nq = len(r.query)
        for j, sid in enumerate(r.ids):
            block = host[i][j]
            nc = block.shape[1]
            got = rows[i][j]
            assert np.array_equal(got[:nq, :nc], block)
            assert not got[nq:].any() and not got[:, nc:].any()
            same = r.query[:, None] == coll.get_set(int(sid))[None, :]
            assert np.all(got[:nq, :nc][same] == 1.0)
            n_identity += int(same.sum())
            n_at_alpha += int((got[:nq, :nc] == np.float32(alpha)).sum())
    assert n_identity > 0 and n_at_alpha > 0       # kept: >= alpha
    for w, nqs, ncs, spans in batches:
        used = sum(hi - lo for lo, hi in spans.values())
        assert not w[used:].any()                  # pow2 pad rows are zero
        assert not nqs[used:].any() and not ncs[used:].any()


@pytest.mark.parametrize("provider", PROVIDERS)
def test_a_rows_weights_do_not_depend_on_its_batch(coll, provider):
    sim = _provider(provider, coll.vocab_size)
    requests = _requests(coll, np.random.default_rng(6), 12)
    pool = VerifierPool(coll, sim, SearchParams(alpha=0.3, verify_batch=8))
    together, _ = _device_rows(pool, requests)
    backwards, _ = _device_rows(pool, requests[::-1])
    n = len(requests)
    for i, r in enumerate(requests):
        alone, _ = _device_rows(pool, [r])
        assert np.array_equal(together[i], alone[0])
        assert np.array_equal(together[i], backwards[n - 1 - i])


def _reference_so(coll, sim, query, sid, alpha):
    """Exact semantic overlap in float64 NumPy/SciPy, independent of the
    code under test."""
    table = np.asarray(sim.table, np.float64)
    cand = coll.get_set(int(sid))
    if sim.name == "cosine":
        t = table / np.maximum(np.linalg.norm(table, axis=1,
                                              keepdims=True), 1e-12)
        s = np.clip(t[query] @ t[cand].T, 0.0, 1.0)
    else:
        qv, tv = table[query], table[cand]
        inter = qv @ tv.T
        union = qv.sum(1)[:, None] + tv.sum(1)[None, :] - inter
        s = np.where(union > 0, inter / np.maximum(union, 1.0), 0.0)
    s = np.where(query[:, None] == cand[None, :], 1.0, s)
    w = np.where(s >= alpha, s, 0.0)
    r, c = linear_sum_assignment(w, maximize=True)
    return w[r, c].sum()


@pytest.mark.parametrize("verifier", VERIFIERS)
@pytest.mark.parametrize("provider", PROVIDERS)
def test_pool_verifies_the_exact_overlap(coll, provider, verifier):
    """Brackets from the device-built weights hold the reference overlap;
    a theta at the median score drives Lemma-8 aborts and the auction's
    exact fallback, which re-packs the ambiguous rows' token ids."""
    sim = _provider(provider, coll.vocab_size)
    alpha = 0.8 if provider == "cosine" else 0.2
    pool = VerifierPool(coll, sim, SearchParams(alpha=alpha, verify_batch=8,
                                                verifier=verifier))
    requests = _requests(coll, np.random.default_rng(7), 6)
    refs = [np.array([_reference_so(coll, sim, r.query, i, alpha)
                      for i in r.ids]) for r in requests]
    for r, ref in zip(requests, refs):
        r.theta_lb = float(np.median(ref))
    tol = 1e-4
    for r, ref, out in zip(requests, refs, pool.verify_requests(requests)):
        ok = ~out.early
        assert np.all(out.lb[ok] <= ref[ok] + tol)
        assert np.all(out.ub[ok] >= ref[ok] - tol)
        # a bracket that is left open cannot straddle theta
        open_ = ok & (out.ub - out.lb > 1e-6)
        assert not np.any(open_ & (out.lb < r.theta_lb)
                          & (out.ub > r.theta_lb))
        if verifier != "auction":
            assert np.allclose(out.lb[ok], ref[ok], atol=tol)
        assert np.all(ref[out.early] < r.theta_lb + tol)   # Lemma 8


@pytest.mark.parametrize("verifier", VERIFIERS)
def test_ngram_search_batch_is_search(coll, verifier):
    """The n-gram provider, served on host waves, takes the device weight
    path too: search_batch == per-query search, bitwise."""
    sim = _provider("ngram", coll.vocab_size)
    params = SearchParams(k=5, alpha=0.2, chunk_size=64, verify_batch=8,
                          verifier=verifier, fused="off")
    engine = KoiosSearch(coll, sim, params, partitions=2)
    queries = sample_queries(coll, 4, seed=8)
    for q, rb in zip(queries, engine.search_batch(queries)):
        rs = engine.search(q)
        assert len(rs.ids) > 0
        assert np.array_equal(rs.ids, rb.ids)
        assert np.array_equal(rs.lb, rb.lb)
        assert np.array_equal(rs.ub, rb.ub)


@pytest.mark.parametrize("provider", PROVIDERS)
def test_a_shared_query_gives_the_per_row_weights(coll, provider):
    """The fused wave's rounds pass one query for the whole candidate
    batch; its weights equal the host pool's per-row form."""
    from repro.core.similarity import device_weights

    sim = _provider(provider, coll.vocab_size)
    rng = np.random.default_rng(9)
    q = np.full(32, -1, np.int32)
    q[:19] = rng.choice(coll.vocab_size, 19, replace=False)
    ids = rng.choice(coll.num_sets, 8, replace=False)
    pool = VerifierPool(coll, sim, SearchParams(alpha=0.3))
    c_tok, ncs = pool._candidate_tokens(ids, 8)
    c_tok[:, 0] = np.where(ncs > 0, q[0], -1)      # an identity pair a row
    alpha = np.float32(0.3)
    shared = device_weights(sim.row_blocks, sim.block_table, q, c_tok,
                            np.int32(19), ncs, alpha)
    per_row = device_weights(sim.row_blocks, sim.block_table,
                             np.broadcast_to(q, (8, 32)), c_tok,
                             np.full(8, 19, np.int32), ncs, alpha)
    assert np.array_equal(np.asarray(shared), np.asarray(per_row))
    assert np.all(np.asarray(shared)[:, 0, 0] == 1.0)
