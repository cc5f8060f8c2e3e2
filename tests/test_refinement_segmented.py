"""Set-segmented refinement scan + device-resident event expansion
(the PR-5 tentpole): the segmented admission schedule and the fused
wave's in-trace expansion are bit-identical to the serial host path,
and the cross-set commutativity the layout rests on holds as a
property."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (InvertedIndex, KoiosSearch, SearchParams,
                        build_token_stream, expand_to_events)
from repro.core.refinement import refine_carry_init, refine_chunk_step, \
    run_refinement
from repro.core.token_stream import (EventStream, event_ranks,
                                     pack_events_segmented, pad_events)
from repro.data import sample_queries


def _valid_events(rng, n_events: int, num_sets: int, nq: int,
                  slots_per_set: int = 8) -> EventStream:
    """Synthetic posting-level events honouring the domain invariant the
    segmented layout rests on: each flat slot belongs to exactly one set."""
    set_id = rng.integers(0, num_sets, n_events).astype(np.int32)
    return EventStream(
        set_id=set_id,
        q_pos=rng.integers(0, nq, n_events).astype(np.int32),
        slot=(set_id * slots_per_set
              + rng.integers(0, slots_per_set, n_events)).astype(np.int32),
        sim=np.sort(rng.random(n_events).astype(np.float32))[::-1],
        n_tuples=n_events)


@pytest.mark.parametrize("ub_mode", ["sound", "paper"])
@pytest.mark.parametrize("chunk", [16, 64, 256])
def test_segmented_matches_serial_bitwise(small_world, ub_mode, chunk):
    """The tentpole guarantee at the scan level: the lane-packed
    segmented admission returns the same floats, bounds, masks, and
    theta as the per-event serial loop, at every chunk size and in both
    ub modes."""
    coll, sim = small_world
    inv = InvertedIndex.build(coll)
    for seed in (3, 11):
        q = sample_queries(coll, 1, seed=seed)[0]
        ev = expand_to_events(build_token_stream(q, sim, 0.8), inv)
        a = run_refinement(ev, coll.set_sizes, len(q), coll.total_tokens,
                           5, 0.8, chunk, ub_mode, layout="serial")
        b = run_refinement(ev, coll.set_sizes, len(q), coll.total_tokens,
                           5, 0.8, chunk, ub_mode, layout="segmented")
        assert np.array_equal(a.S, b.S)
        assert np.array_equal(a.ub, b.ub)
        assert np.array_equal(a.seen, b.seen)
        assert np.array_equal(a.alive, b.alive)
        assert a.theta_lb == b.theta_lb
        assert a.stats.pruned_refinement == b.stats.pruned_refinement


@pytest.mark.parametrize("partitions", [1, 2, 4])
def test_segmented_engine_bitwise(small_world, partitions):
    """End-to-end: an engine on the segmented layout returns results
    bit-identical to the serial layout on both wave kinds."""
    coll, sim = small_world
    queries = sample_queries(coll, 4, seed=5)
    results = {}
    for layout in ("serial", "segmented"):
        for fused in ("off", "interpret"):
            params = SearchParams(k=5, alpha=0.8, chunk_size=64,
                                  verify_batch=8, refine_layout=layout,
                                  fused=fused)
            engine = KoiosSearch(coll, sim, params, partitions=partitions)
            results[(layout, fused)] = engine.search_batch(queries)
    base = results[("serial", "off")]
    for key, rs in results.items():
        for a, b in zip(base, rs):
            assert np.array_equal(a.ids, b.ids), key
            assert np.array_equal(a.lb, b.lb), key
            assert np.array_equal(a.ub, b.ub), key


@pytest.mark.parametrize("layout", ["serial", "segmented"])
def test_fused_device_expansion_bitwise(small_world, layout):
    """The fused wave consumes the compact stream and expands in-trace
    (DESIGN.md §3.3); with either embedded admission layout the results
    must equal the host path bit for bit."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret", refine_layout=layout)
    engine = KoiosSearch(coll, sim, params, partitions=2)
    queries = sample_queries(coll, 4, seed=5)
    seq = KoiosSearch(coll, sim, dataclasses.replace(params, fused="off"),
                      partitions=2).search_batch(queries)
    fus = engine.search_batch(queries)
    assert engine.scheduler_stats.schedule == "fused"
    for a, b in zip(seq, fus):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.lb, b.lb)
        assert np.array_equal(a.ub, b.ub)


def test_expand_events_traced_mirrors_host(small_world):
    """The searchsorted-on-cumsum gather reproduces
    ``expand_to_events`` + ``pad_events`` bit for bit — including extra
    pad chunks and the empty-stream 0.0 pad."""
    from repro.core.wave import expand_events_traced

    coll, sim = small_world
    inv = InvertedIndex.build(coll)
    dev = inv.device_arrays()
    chunk = 64
    for seed in (3, 5):
        q = sample_queries(coll, 1, seed=seed)[0]
        stream = build_token_stream(q, sim, 0.8)
        host = pad_events(expand_to_events(stream, inv), chunk)
        n_chunks = host[0].shape[0] * 2      # extra all-pad chunks too
        tok = np.full(128, -1, np.int32)
        qp = np.zeros(128, np.int32)
        sm = np.zeros(128, np.float32)
        tok[:len(stream)] = stream.token
        qp[:len(stream)] = stream.q_pos
        sm[:len(stream)] = stream.sim
        es, eq, esl, esim = [np.asarray(x) for x in expand_events_traced(
            jnp.asarray(tok), jnp.asarray(qp), jnp.asarray(sm),
            *dev, n_chunks, chunk)]
        n = host[0].shape[0]
        assert np.array_equal(es[:n], host[0])
        assert np.array_equal(eq[:n], host[1])
        assert np.array_equal(esl[:n], host[2])
        assert np.array_equal(esim[:n], host[3])
        # extra pad chunks: sentinel sets, final-sim fill
        assert np.all(es[n:] == -1)
        assert np.all(esim[n:] == host[3][-1, -1])
    # empty stream: no postings, sims pad 0.0 (the pad_events fix)
    empty = expand_events_traced(
        jnp.full(8, -1, jnp.int32), jnp.zeros(8, jnp.int32),
        jnp.zeros(8, jnp.float32), *dev, 1, chunk)
    assert np.all(np.asarray(empty[0]) == -1)
    assert np.all(np.asarray(empty[3]) == 0.0)


def test_empty_stream_full_scan():
    """Regression (PR-5 satellite): an empty stream pads sims with 0.0,
    and the full refinement scan is inert on it in both layouts."""
    empty = EventStream(set_id=np.zeros(0, np.int32),
                        q_pos=np.zeros(0, np.int32),
                        slot=np.zeros(0, np.int32),
                        sim=np.zeros(0, np.float32), n_tuples=0)
    padded = pad_events(empty, 16)
    assert padded[0].shape == (1, 16)
    assert np.all(padded[0] == -1)
    assert np.all(padded[3] == 0.0)          # NOT the historical 1.0
    sizes = np.full(10, 4, np.int32)
    for layout in ("serial", "segmented"):
        r = run_refinement(empty, sizes, 4, 40, 3, 0.8, 16, "sound",
                           layout=layout)
        assert not r.seen.any()
        assert r.alive.all()
        assert r.theta_lb == 0.0
        assert np.all(r.S == 0.0)
        assert r.stats.pruned_refinement == 0


def _admission_fields(state):
    """Carry fields written by admission (everything except alive and
    theta, which the chunk filter pass owns)."""
    S, l, T, d, seen, alive, qm, qs, sm, theta = state
    return [np.asarray(x) for x in (S, l, T, d, seen, qm, qs, sm)]


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(20, 200))
def test_cross_set_permutation_leaves_carry_bit_identical(seed, n_events):
    """THE invariant the segmented layout rests on: permuting a chunk's
    events across sets — while preserving each set's own order — leaves
    the admission carry bit-identical, because every mutated field is
    per-set and each flat slot belongs to exactly one set."""
    rng = np.random.default_rng(seed)
    num_sets, nq, chunk = 12, 16, 256
    ev = _valid_events(rng, n_events, num_sets, nq)
    es, eq, esl, esim = pad_events(ev, chunk)
    # cross-set permutation of chunk 0: stable sort by a random per-set
    # key (ties keep stream order, so within-set order is preserved)
    key = rng.permutation(num_sets + 1)
    perm = np.argsort(key[es[0] + 1], kind="stable")
    assert (es[0].min() == -1) or len(set(es[0])) == 1 or \
        not np.array_equal(perm, np.arange(chunk)) or n_events < 2

    cap = jnp.full((num_sets,), min(nq, 8), jnp.int32)
    state0 = refine_carry_init(num_sets, 1, num_sets * 8)
    out_a, _ = refine_chunk_step(
        state0, (jnp.asarray(es[0]), jnp.asarray(eq[0]),
                 jnp.asarray(esl[0]), jnp.asarray(esim[0])),
        cap, 3, "sound")
    out_b, _ = refine_chunk_step(
        state0, (jnp.asarray(es[0][perm]), jnp.asarray(eq[0][perm]),
                 jnp.asarray(esl[0][perm]), jnp.asarray(esim[0][perm])),
        cap, 3, "sound")
    for a, b in zip(_admission_fields(out_a), _admission_fields(out_b)):
        assert np.array_equal(a, b)


def test_event_ranks_are_within_set_occurrence_indices():
    """Host ranks == traced ranks == the occurrence index of each event
    within its (chunk, set) segment."""
    from repro.kernels.ref import event_ranks_ref

    rng = np.random.default_rng(7)
    ev = _valid_events(rng, 300, 9, 8)
    es = pad_events(ev, 64)[0]
    ranks = event_ranks(es)
    for c in range(es.shape[0]):
        # brute-force occurrence index
        counts = {}
        for j, s in enumerate(es[c]):
            expect = counts.get(s, 0)
            counts[s] = expect + 1
            assert ranks[c, j] == expect, (c, j, s)
        traced = np.asarray(event_ranks_ref(jnp.asarray(es[c])))
        assert np.array_equal(traced, ranks[c])


def test_pack_events_segmented_layout():
    """Lane packing invariants: every valid event appears exactly once,
    rows hold pairwise-distinct sets, and row index == within-set rank."""
    rng = np.random.default_rng(3)
    ev = _valid_events(rng, 500, 17, 8)
    padded = pad_events(ev, 128)
    s3, q3, sl3, si3, snow = pack_events_segmented(*padded)
    assert np.array_equal(snow, padded[3][:, -1])
    n_chunks = padded[0].shape[0]
    W, L = s3.shape[1], s3.shape[2]
    assert W & (W - 1) == 0 and L & (L - 1) == 0
    total_valid = int((padded[0] >= 0).sum())
    assert int((s3 >= 0).sum()) == total_valid
    for c in range(n_chunks):
        for t in range(W):
            row = s3[c, t][s3[c, t] >= 0]
            assert len(np.unique(row)) == len(row)   # distinct sets per row
        # row index is the within-set rank: counting occurrences of a set
        # down the rows reproduces its segment length
        flat = padded[0][c]
        for s in np.unique(flat[flat >= 0]):
            seg = int((flat == s).sum())
            rows_with_s = [t for t in range(W) if s in s3[c, t]]
            assert rows_with_s == list(range(seg))
