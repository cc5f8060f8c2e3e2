"""The one traffic generator: turns a traffic file's parameters and a
seed into a schedule of requests.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

``loop``
    ``"open"``: requests arrive at scheduled times whatever the system
    does (independent users); ``"closed"``: ``clients`` callers each
    send their next request when the previous one is answered.
``rate_per_s`` (open)
    Offered load.  Gaps between arrivals are the exponential
    distribution's quantiles at (i + 1/2) / n, in an order drawn from
    the seed: a Poisson stream whose set of gaps is the same for every
    seed, so runs differ in order and not in load.
``clients`` (closed)
    Number of callers, each with one request outstanding.
``queries``
    ``"fresh"``: every request asks a different set of the corpus: one
    draw from each of n equal strata of the corpus ordered by size, made
    once from a fixed seed (``fixed_sets``), so every run asks for the
    same work; the run's seed changes the embedding values.
    ``"zipf"``: requests repeat a pool of ``pool`` such sets, rank r
    drawn with probability proportional to r ** -``zipf_a`` (again by
    quantiles, in a seeded order).
``warmup``
    Open loop, ``{"cohorts": c, "seconds": s}``: set-up serves doubling
    cohorts of up to c requests, then replays this mix for s seconds
    from a fixed seed.  Closed loop, ``{"cover": f}``: the callers start
    together and the engine keeps them in step (every request takes one
    engine step per shard), so the window serves the schedule in
    cohorts of ``clients`` consecutive requests; set-up serves those
    cohorts in order until their serving time, compiles aside, reaches
    f times the window.  That compiles every program the window's
    cohorts use, with a margin of f - 1 for serving that runs faster in
    the window than in set-up, and nothing else.
``check``
    Number of answered requests whose top-k is compared with the
    reference after the window: all of them where the window answers no
    more.  The control (``control.py``) compares the schedule's first
    ``check`` requests.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# Seed of the warm-up traffic: the same for every run, so set-up does
# the same work each time and every program it compiles is cached.
WARMUP_SEED = 7_777_777
# Seed of the sets ``fresh`` requests ask (see fixed_sets).
PROFILE_SEED = 5_555_555


@dataclasses.dataclass
class Schedule:
    """Requests of one run: ``sets[i]`` is the corpus set asked by
    request i; for an open loop ``offsets[i]`` is its arrival in seconds
    after the window opens (ascending), for a closed loop requests are
    sent in index order as callers free up."""

    loop: str
    sets: np.ndarray
    offsets: np.ndarray
    clients: int = 0


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), salt])


def stratified_sets(set_sizes: np.ndarray, n: int,
                    rng: np.random.Generator) -> np.ndarray:
    """n distinct set ids, one uniform pick from each of n equal strata
    of the corpus ordered by size, in random order."""
    n = min(int(n), len(set_sizes))
    by_size = np.argsort(set_sizes, kind="stable")
    cuts = np.linspace(0, len(by_size), n + 1).astype(np.int64)
    picks = cuts[:-1] + (rng.random(n) * np.diff(cuts)).astype(np.int64)
    return rng.permutation(by_size[picks])


def fixed_sets(set_sizes: np.ndarray, n: int) -> np.ndarray:
    """The n sets a ``fresh`` mix asks, in order: one uniform pick from
    each of n equal strata of the corpus ordered by size, from a fixed
    seed.  The same for every run: the work a closed loop's cohorts do
    is set by their largest members, so requests chosen by the run's
    seed changed the rate by up to 17% from seed to seed on a TPU v5e; the
    seed varies the embedding values instead, which every similarity
    and score depends on."""
    return stratified_sets(set_sizes, n, _rng(PROFILE_SEED, 6))


def quantile_gaps(n: int, rate: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """n exponential gaps of mean 1/rate, at the quantiles (i+1/2)/n, in
    random order."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / rate)


def zipf_ranks(n: int, pool: int, a: float, rng: np.random.Generator
               ) -> np.ndarray:
    """n ranks in [0, pool) with P(r) ~ (r+1)**-a, at quantiles, in
    random order."""
    p = np.arange(1, pool + 1, dtype=np.float64) ** (-a)
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(np.minimum(np.searchsorted(cdf, u), pool - 1))


def pool_sets(mix: dict, set_sizes: np.ndarray, seed: int) -> np.ndarray:
    """The pool a ``zipf`` mix repeats (empty for ``fresh``)."""
    if mix["queries"] != "zipf":
        return np.zeros(0, np.int64)
    return fixed_sets(set_sizes, int(mix["pool"]))


def schedule(mix: dict, set_sizes: np.ndarray, seed: int,
             seconds: float) -> Schedule:
    """The requests of a run of ``seconds`` with this mix and seed."""
    loop = mix["loop"]
    if loop == "open":
        n = max(1, math.ceil(float(mix["rate_per_s"]) * seconds))
        gaps = quantile_gaps(n, float(mix["rate_per_s"]), _rng(seed, 1))
        offsets = np.cumsum(gaps)
    elif loop == "closed":
        n = min(len(set_sizes), int(mix["max_requests"]))
        offsets = np.zeros(0)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    if mix["queries"] == "fresh":
        sets = fixed_sets(set_sizes, n)
    elif mix["queries"] == "zipf":
        pool = pool_sets(mix, set_sizes, seed)
        sets = pool[zipf_ranks(n, len(pool), float(mix["zipf_a"]),
                               _rng(seed, 4))]
    else:
        raise ValueError(f"unknown queries {mix['queries']!r}")
    if loop == "open" and len(sets) < n:
        raise ValueError(f"the corpus holds {len(sets)} sets, the window "
                         f"needs {n} distinct ones")
    return Schedule(loop=loop, sets=sets, offsets=offsets,
                    clients=int(mix.get("clients", 0)))


def closed_cohorts(sched: Schedule, n: int) -> list:
    """The first ``n`` cohorts a closed loop of ``sched.clients``
    callers started together sends: consecutive runs of the schedule."""
    c = sched.clients
    return [sched.sets[i * c:(i + 1) * c] for i in range(n)
            if (i + 1) * c <= len(sched.sets)]


def warmup_schedule(mix: dict, set_sizes: np.ndarray) -> Schedule:
    """The replay that set-up serves: this mix from the fixed warm-up
    seed, fresh sets whatever the mix repeats."""
    fresh = dict(mix, queries="fresh")
    return schedule(fresh, set_sizes, WARMUP_SEED,
                    float(mix["warmup"]["seconds"]))


def warmup_cohort(mix: dict, set_sizes: np.ndarray) -> np.ndarray:
    """Set ids of the doubling-cohort warm-up (fixed seed)."""
    return stratified_sets(set_sizes, int(mix["warmup"]["cohorts"]),
                           _rng(WARMUP_SEED, 5))
