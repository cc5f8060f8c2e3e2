"""The traffic generator: schedules from a seed."""
import math

import numpy as np
import pytest

import _paths  # noqa: F401
import loadgen

SIZES = np.random.default_rng(0).integers(2, 152, size=27204)
BIG_SEED = 2**31 + 987_654_321


def test_open_poisson_count_and_rate():
    mix = {"loop": "open", "rate_per_s": 6.5, "queries": "fresh"}
    s = loadgen.schedule(mix, SIZES, BIG_SEED, 30.0)
    assert len(s.sets) == len(s.offsets) == math.ceil(6.5 * 30)
    assert np.all(np.diff(s.offsets) > 0)
    gaps = np.diff(np.concatenate([[0.0], s.offsets]))
    assert abs(gaps.mean() - 1 / 6.5) < 0.05 / 6.5
    # exponential: the coefficient of variation is about 1
    assert 0.85 < gaps.std() / gaps.mean() < 1.1


def test_same_seed_same_schedule_other_seed_same_gaps():
    mix = {"loop": "open", "rate_per_s": 4.0, "queries": "fresh"}
    a = loadgen.schedule(mix, SIZES, BIG_SEED, 20.0)
    b = loadgen.schedule(mix, SIZES, BIG_SEED, 20.0)
    c = loadgen.schedule(mix, SIZES, 17, 20.0)
    assert np.array_equal(a.sets, b.sets)
    assert np.array_equal(a.offsets, b.offsets)
    assert not np.array_equal(a.offsets, c.offsets)
    ga = np.sort(np.diff(np.concatenate([[0.0], a.offsets])))
    gc = np.sort(np.diff(np.concatenate([[0.0], c.offsets])))
    assert np.allclose(ga, gc)


def test_fresh_never_repeats_and_spans_sizes():
    mix = {"loop": "open", "rate_per_s": 20.0, "queries": "fresh"}
    s = loadgen.schedule(mix, SIZES, 3, 30.0)
    assert len(np.unique(s.sets)) == len(s.sets)
    q = np.quantile(SIZES[s.sets], [0.1, 0.5, 0.9])
    assert np.allclose(q, np.quantile(SIZES, [0.1, 0.5, 0.9]), rtol=0.1)


def test_fresh_refuses_more_requests_than_sets():
    mix = {"loop": "open", "rate_per_s": 100.0, "queries": "fresh"}
    with pytest.raises(ValueError):
        loadgen.schedule(mix, SIZES[:50], 1, 10.0)


def test_zipf_ranks_follow_the_exponent():
    ranks = loadgen.zipf_ranks(20000, 256, 1.1, np.random.default_rng(1))
    counts = np.bincount(ranks, minlength=256)
    assert ranks.min() >= 0 and ranks.max() < 256
    assert counts[0] == counts.max()
    p = np.arange(1, 257) ** -1.1
    p /= p.sum()
    assert abs(counts[0] / 20000 - p[0]) < 0.01
    assert abs(counts[9] / 20000 - p[9]) < 0.005


def test_zipf_mix_repeats_its_pool():
    mix = {"loop": "open", "rate_per_s": 10.0, "queries": "zipf",
           "pool": 256, "zipf_a": 1.1}
    s = loadgen.schedule(mix, SIZES, BIG_SEED, 30.0)
    pool = loadgen.pool_sets(mix, SIZES, BIG_SEED)
    assert len(np.unique(pool)) == 256
    assert set(s.sets.tolist()) <= set(pool.tolist())
    assert len(np.unique(s.sets)) < len(s.sets)


def test_closed_loop_clients_and_requests():
    mix = {"loop": "closed", "clients": 64, "queries": "fresh",
           "max_requests": 4096}
    s = loadgen.schedule(mix, SIZES[:4246], BIG_SEED, 30.0)
    assert s.clients == 64
    assert len(s.sets) == 4096 and len(np.unique(s.sets)) == 4096
    assert len(s.offsets) == 0


def test_warmup_traffic_is_fixed_and_fresh():
    mix = {"loop": "open", "rate_per_s": 4.0, "queries": "zipf",
           "pool": 16, "zipf_a": 1.1,
           "warmup": {"cohorts": 8, "seconds": 10}}
    a = loadgen.warmup_schedule(mix, SIZES)
    b = loadgen.warmup_schedule(mix, SIZES)
    assert np.array_equal(a.sets, b.sets)
    assert len(np.unique(a.sets)) == len(a.sets) == 40
    assert np.array_equal(loadgen.warmup_cohort(mix, SIZES),
                          loadgen.warmup_cohort(mix, SIZES))


def test_fresh_sets_are_the_same_for_every_seed():
    mix = {"loop": "closed", "clients": 4, "queries": "fresh",
           "max_requests": 400}
    a = loadgen.schedule(mix, SIZES, 1, 30.0)
    b = loadgen.schedule(mix, SIZES, BIG_SEED, 30.0)
    assert np.array_equal(a.sets, b.sets)


def test_closed_cohorts_are_consecutive_runs():
    mix = {"loop": "closed", "clients": 4, "queries": "fresh",
           "max_requests": 10}
    s = loadgen.schedule(mix, SIZES, 3, 30.0)
    cohorts = loadgen.closed_cohorts(s, 5)
    assert len(cohorts) == 2
    assert np.array_equal(np.concatenate(cohorts), s.sets[:8])
