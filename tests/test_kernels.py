"""Per-kernel allclose vs the ref.py oracles (interpret mode, requested
by every call), with shape/dtype sweeps + hypothesis randomization."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.wave import compact_indices
from repro.kernels import (auction_topk2, auction_topk2_ref, cosine_topk,
                           cosine_topk_ref, ssd, ssd_ref)


def _unit(rng, n, d, dtype=np.float32):
    x = rng.normal(size=(n, d)).astype(dtype)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


# ------------------------------------------------------------- cosine_topk
@pytest.mark.parametrize("nq,nv,d,k,bv", [
    (4, 64, 16, 4, 16),
    (8, 100, 32, 8, 32),      # nv not a multiple of bv (padding path)
    (3, 257, 8, 16, 64),
    (16, 512, 128, 32, 128),
])
def test_cosine_topk_shapes(nq, nv, d, k, bv):
    rng = np.random.default_rng(0)
    qe, ev = _unit(rng, nq, d), _unit(rng, nv, d)
    vals, idx = cosine_topk(qe, ev, k=k, bv=bv, interpret=True)
    rvals, ridx = cosine_topk_ref(jnp.asarray(qe), jnp.asarray(ev), k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               atol=1e-5, rtol=1e-5)
    # indices must agree where the scores are strictly separated
    sep = np.asarray(rvals)[:, :-1] - np.asarray(rvals)[:, 1:] > 1e-5
    same = np.asarray(idx)[:, :-1] == np.asarray(ridx)[:, :-1]
    assert np.all(same | ~sep)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_cosine_topk_dtypes(dtype):
    rng = np.random.default_rng(1)
    qe, ev = _unit(rng, 4, 16, dtype), _unit(rng, 64, 16, dtype)
    vals, _ = cosine_topk(qe, ev, k=4, bv=16, interpret=True)
    rvals, _ = cosine_topk_ref(jnp.asarray(qe, jnp.float32),
                               jnp.asarray(ev, jnp.float32), 4)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(2, 40),
       st.integers(1, 6))
def test_cosine_topk_property(seed, nq, nv, k):
    k = min(k, nv)
    rng = np.random.default_rng(seed)
    qe, ev = _unit(rng, nq, 8), _unit(rng, nv, 8)
    vals, _ = cosine_topk(qe, ev, k=k, bv=8, interpret=True)
    rvals, _ = cosine_topk_ref(jnp.asarray(qe), jnp.asarray(ev), k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               atol=1e-5)


# ------------------------------------------------------------ auction_topk2
@pytest.mark.parametrize("n,m,bn", [(8, 16, 4), (100, 33, 32), (5, 7, 8)])
def test_auction_topk2_shapes(n, m, bn):
    rng = np.random.default_rng(2)
    wm = rng.random((n, m)).astype(np.float32)
    prices = rng.random(m).astype(np.float32)
    w1, w2, j = auction_topk2(wm, prices, bn=bn, interpret=True)
    rw1, rw2, rj = auction_topk2_ref(jnp.asarray(wm), jnp.asarray(prices))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(rw1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(rw2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(j), np.asarray(rj))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 20), st.integers(2, 20))
def test_auction_topk2_property(seed, n, m):
    rng = np.random.default_rng(seed)
    wm = np.where(rng.random((n, m)) > 0.5, rng.random((n, m)), 0.0)
    wm = wm.astype(np.float32)
    prices = (rng.random(m) * 2).astype(np.float32)
    w1, w2, j = auction_topk2(wm, prices, bn=8, interpret=True)
    rw1, rw2, rj = auction_topk2_ref(jnp.asarray(wm), jnp.asarray(prices))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(rw1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(rw2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(j), np.asarray(rj))


# --------------------------------------------------------------------- ssd
def _ssd_inputs(rng, Bt, L, H, P, G, S):
    x = rng.normal(size=(Bt, L, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(Bt, L, H)))).astype(np.float32)
    A = (-np.exp(rng.normal(size=H))).astype(np.float32)
    B = rng.normal(size=(Bt, L, G, S)).astype(np.float32) / np.sqrt(S)
    C = rng.normal(size=(Bt, L, G, S)).astype(np.float32) / np.sqrt(S)
    D = rng.normal(size=H).astype(np.float32)
    return x, dt, A, B, C, D


@pytest.mark.parametrize("L,chunk", [(8, 4), (16, 8), (12, 8)])  # 12: pad path
@pytest.mark.parametrize("H,G", [(2, 1), (4, 2)])
def test_ssd_vs_ref(L, chunk, H, G):
    rng = np.random.default_rng(3)
    Bt, P, S = 2, 4, 8
    x, dt, A, B, C, D = _ssd_inputs(rng, Bt, L, H, P, G, S)
    y = ssd(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    yr = np.stack([np.asarray(ssd_ref(jnp.asarray(x[b]), jnp.asarray(dt[b]),
                                      jnp.asarray(A), jnp.asarray(B[b]),
                                      jnp.asarray(C[b]), jnp.asarray(D)))
                   for b in range(Bt)])
    np.testing.assert_allclose(np.asarray(y), yr, atol=2e-4, rtol=2e-4)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 3))
def test_ssd_property(seed, Bt):
    rng = np.random.default_rng(seed)
    L, H, P, G, S = 8, 2, 4, 2, 4
    x, dt, A, B, C, D = _ssd_inputs(rng, Bt, L, H, P, G, S)
    y = ssd(x, dt, A, B, C, D, chunk=4, interpret=True)
    yr = np.stack([np.asarray(ssd_ref(jnp.asarray(x[b]), jnp.asarray(dt[b]),
                                      jnp.asarray(A), jnp.asarray(B[b]),
                                      jnp.asarray(C[b]), jnp.asarray(D)))
                   for b in range(Bt)])
    np.testing.assert_allclose(np.asarray(y), yr, atol=2e-4, rtol=2e-4)
    assert not np.any(np.isnan(np.asarray(y)))


# --------------------------------------------------------- flash attention
from repro.kernels import flash_attention, flash_attention_ref  # noqa: E402


@pytest.mark.parametrize("S,bq,bk,causal", [
    (16, 8, 8, True),
    (24, 8, 16, True),
    (20, 8, 8, False),     # padded-KV mask path
    (17, 8, 16, True),     # both paddings
])
def test_flash_attention_vs_ref(S, bq, bk, causal):
    rng = np.random.default_rng(0)
    B, H, d = 2, 2, 8
    q = rng.normal(size=(B, H, S, d)).astype(np.float32)
    k = rng.normal(size=(B, H, S, d)).astype(np.float32)
    v = rng.normal(size=(B, H, S, d)).astype(np.float32)
    out = flash_attention(q, k, v, bq=bq, bk=bk, causal=causal,
                          interpret=True)
    ref_out = flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 20), st.booleans())
def test_flash_attention_property(seed, S, causal):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(1, 1, S, 8)).astype(np.float32)
    k = rng.normal(size=(1, 1, S, 8)).astype(np.float32)
    v = rng.normal(size=(1, 1, S, 8)).astype(np.float32)
    out = flash_attention(q, k, v, bq=8, bk=8, causal=causal,
                          interpret=True)
    ref_out = flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-5, rtol=1e-5)


# --------------------------------------------------------- compact_indices
@pytest.mark.parametrize("n,p", [(1, 1.0), (1, 0.0), (7, 0.5), (64, 0.25),
                                 (120, 0.9), (255, 0.0)])
def test_compact_indices_vs_ref(n, p):
    rng = np.random.default_rng(n)
    mask = rng.random(n) < p
    idx, cnt = compact_indices(jnp.asarray(mask))
    assert int(cnt) == int(mask.sum())
    # the contract the wave program relies on: ascending survivor ids,
    # -1 beyond the count — exactly mask.nonzero()[0]
    assert np.array_equal(np.asarray(idx)[:int(cnt)], np.nonzero(mask)[0])
    assert np.all(np.asarray(idx)[int(cnt):] == -1)


def test_compact_indices_vmap_under_jit():
    rng = np.random.default_rng(3)
    masks = rng.random((5, 33)) < 0.4
    f = jax.jit(jax.vmap(compact_indices))
    idx, cnt = f(jnp.asarray(masks))
    for b in range(len(masks)):
        assert np.array_equal(np.asarray(idx)[b, :int(cnt[b])],
                              np.nonzero(masks[b])[0])


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 200))
def test_compact_indices_property(seed, n):
    rng = np.random.default_rng(seed)
    mask = rng.random(n) < rng.random()
    idx, cnt = compact_indices(jnp.asarray(mask))
    assert np.array_equal(np.asarray(idx)[:int(cnt)], np.nonzero(mask)[0])
    assert np.all(np.asarray(idx)[int(cnt):] == -1)


# ------------------------------------------- auction round kernel (fused-in)
def test_auction_batch_kernel_parity():
    """auction_batch(use_kernel=True) routes every bidding round's profit
    top-2 through the Pallas kernel (the fused-wave TPU path); brackets
    must match the inline jnp pass bit for bit (same tie-breaking)."""
    from repro.core.matching.auction import auction_batch, make_eps_schedule
    rng = np.random.default_rng(0)
    B, N, M = 3, 4, 12
    w = np.where(rng.random((B, N, M)) > 0.5, rng.random((B, N, M)), 0.0)
    w = w.astype(np.float32)
    nq = np.array([4, 3, 2], np.int32)
    nc = np.array([12, 7, 12], np.int32)
    eps = make_eps_schedule(1e-4)
    ref_res = auction_batch(jnp.asarray(w), jnp.asarray(nq),
                            jnp.asarray(nc), eps, jnp.float32(-1e30))
    ker_res = auction_batch(jnp.asarray(w), jnp.asarray(nq),
                            jnp.asarray(nc), eps, jnp.float32(-1e30),
                            use_kernel=True, interpret=True)
    assert np.array_equal(np.asarray(ref_res.lb), np.asarray(ker_res.lb))
    assert np.array_equal(np.asarray(ref_res.ub), np.asarray(ker_res.ub))
    assert np.array_equal(np.asarray(ref_res.assign),
                          np.asarray(ker_res.assign))


# ------------------------------------------------------------ refine_events
def _refine_chunks(seed, n_events, num_sets=24, nq=16, slots_per_set=8,
                   chunk=64):
    from repro.core.token_stream import (EventStream,
                                         pack_events_segmented, pad_events)

    rng = np.random.default_rng(seed)
    set_id = rng.integers(0, num_sets, n_events).astype(np.int32)
    ev = EventStream(
        set_id=set_id,
        q_pos=rng.integers(0, nq, n_events).astype(np.int32),
        # the domain invariant the layout rests on: each flat slot
        # belongs to exactly one set
        slot=(set_id * slots_per_set
              + rng.integers(0, slots_per_set, n_events)).astype(np.int32),
        sim=np.sort(rng.random(n_events).astype(np.float32))[::-1],
        n_tuples=n_events)
    return (pack_events_segmented(*pad_events(ev, chunk)),
            num_sets, num_sets * slots_per_set)


@pytest.mark.parametrize("seed,n_events", [(0, 120), (1, 500), (2, 37)])
def test_refine_events_vs_ref(seed, n_events):
    """The VMEM-resident admission kernel (interpret mode) is bit-equal
    to the packed jnp oracle — the production segmented path — across a
    multi-chunk carry chain."""
    from repro.kernels import refine_events, refine_events_packed_ref

    from repro.core.refinement import refine_carry_init

    (s3, q3, sl3, si3, _snow), num_sets, total_slots = \
        _refine_chunks(seed, n_events)
    state = refine_carry_init(num_sets, 1, total_slots)[:-1]
    for c in range(s3.shape[0]):
        want = refine_events_packed_ref(
            state, jnp.asarray(s3[c]), jnp.asarray(q3[c]),
            jnp.asarray(sl3[c]), jnp.asarray(si3[c]))
        got = refine_events(state, s3[c], q3[c], sl3[c], si3[c],
                            interpret=True)
        for a, b in zip(want, got):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # thread the carry (alive stays all-true between chunks here)
        state = want[:5] + (state[5],) + want[5:]
    assert bool(np.asarray(state[4]).any())      # something was admitted


def test_refine_events_compiled_raises():
    """Mosaic refuses the admission kernel, so a compiled call must fail
    loudly at dispatch rather than first on the chip."""
    from repro.kernels import refine_events

    from repro.core.refinement import refine_carry_init

    (s3, q3, sl3, si3, _snow), num_sets, total_slots = _refine_chunks(0, 40)
    state = refine_carry_init(num_sets, 1, total_slots)[:-1]
    with pytest.raises(NotImplementedError, match="interpret=True"):
        refine_events(state, s3[0], q3[0], sl3[0], si3[0])


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 300))
def test_refine_events_property(seed, n_events):
    from repro.kernels import refine_events, refine_events_packed_ref

    from repro.core.refinement import refine_carry_init

    (s3, q3, sl3, si3, _snow), num_sets, total_slots = \
        _refine_chunks(seed, n_events, num_sets=9, nq=40, chunk=128)
    rng = np.random.default_rng(seed + 1)
    alive = jnp.asarray(rng.random(num_sets) > 0.3)
    st0 = refine_carry_init(num_sets, 2, total_slots)
    state = st0[:5] + (alive,) + st0[6:-1]
    want = refine_events_packed_ref(
        state, jnp.asarray(s3[0]), jnp.asarray(q3[0]),
        jnp.asarray(sl3[0]), jnp.asarray(si3[0]))
    got = refine_events(state, s3[0], q3[0], sl3[0], si3[0],
                        interpret=True)
    for a, b in zip(want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
