"""chip_smoke.py off the chip: it refuses a CPU backend, and its reference
comparison and hash checks work at a tiny size (fused waves in interpret
mode), agreeing with ``brute_force_topk`` and reporting a wrong top-k."""
import dataclasses
import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import KoiosIndex, SearchParams, brute_force_topk
from repro.data import sample_queries
from repro.runtime.collection import ShardedCollection

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def world(small_world, smoke):
    coll, sim = small_world
    emb = np.asarray(sim.table)
    queries = sample_queries(coll, 4, seed=1)
    refs = [smoke.reference_scores(coll, emb, q, 0.8) for q in queries]
    return coll, sim, queries, refs


def _params(**kw):
    return SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                        fused="interpret", **kw)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_reference_agrees_with_brute_force(world, smoke):
    coll, sim, queries, refs = world
    index = KoiosIndex.build(coll)
    params = _params()
    for q, ref in zip(queries, refs):
        bf = brute_force_topk(index, q, sim, params)
        assert smoke.topk_mismatches(bf.ids, bf.lb, ref, params.k) == []


@pytest.mark.parametrize("verifier", ["hungarian", "auction"])
def test_fused_vs_host_pass(world, smoke, verifier):
    coll, sim, queries, refs = world
    collection = ShardedCollection.build(coll, 4)
    h = smoke.fused_vs_host(collection, sim, _params(verifier=verifier),
                            queries, refs, log=lambda *a: None)
    assert len(h) == 16


def test_placed_phase_one_device(world, smoke):
    """The four-chip phase's code path, on the one CPU device."""
    import jax

    coll, sim, queries, refs = world
    h = smoke.placed_vs_one_chip(coll, sim, _params(), queries, refs,
                                 jax.devices()[:1], log=lambda *a: None)
    assert len(h) == 16


@pytest.mark.parametrize("perturb", ["score", "swap_id", "drop_best"])
def test_perturbed_topk_is_reported(world, smoke, perturb):
    coll, sim, queries, refs = world
    ref = refs[0]
    ids, scores = smoke.reference_topk(ref, 5)
    assert smoke.topk_mismatches(ids, scores, ref, 5) == []
    ids, scores = ids.copy(), scores.copy()
    if perturb == "score":
        scores[2] += 1e-3
    elif perturb == "swap_id":
        worst = min(ref, key=ref.get)          # a set far below the k-th
        ids[-1] = worst
    else:
        ids = np.concatenate([ids[1:], [min(ref, key=ref.get)]])
    assert smoke.topk_mismatches(ids, scores, ref, 5) != []


def test_compile_cache_dir():
    """The persistent cache goes where JAX_COMPILATION_CACHE_DIR says,
    else to a fixed, gitignored directory in the checkout."""
    from repro.runtime.compile_cache import compile_cache_dir

    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x/y"}) == "/x/y"
    path = pathlib.Path(compile_cache_dir({}))
    assert path == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_auction_tolerance_is_wider(smoke):
    q = np.arange(20)
    hung = SearchParams(verifier="hungarian")
    auc = dataclasses.replace(hung, verifier="auction")
    assert smoke.score_tol(q, hung) == smoke.SCORE_TOL
    assert smoke.score_tol(q, auc) == pytest.approx(
        smoke.SCORE_TOL + 20 * auc.auction_eps)
