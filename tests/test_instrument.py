"""Program spans and counters (``repro.runtime.instrument``): nesting and
self time, the off path, the names the benchmark's trace reducer and
metrics rely on, and the spans and filter counters of one engine run."""
import importlib.util
import pathlib
import re

import numpy as np
import pytest

from repro.core import SearchParams
from repro.core.postprocess import VerifierPool, VerifyRequest
from repro.data import sample_queries
from repro.runtime import instrument
from repro.runtime.engine import RequestEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = ROOT / "src" / "repro"
_spec = importlib.util.spec_from_file_location(
    "bench_trace_reduce", ROOT / "bench" / "trace_reduce.py")
trace_reduce = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_reduce)


@pytest.fixture
def clock(monkeypatch):
    """An injected nanosecond clock: ``clock.t`` is what the next read
    returns."""
    class Clock:
        t = 0

        def __call__(self):
            return self.t

    c = Clock()
    monkeypatch.setattr(instrument, "_clock", c)
    return c


def test_nested_spans_count_entries_totals_and_self_time(clock):
    with instrument.counting() as c:
        with instrument.span("koios.step"):           # 0 .. 100
            clock.t = 10
            with instrument.span("koios.wave", shard=0, B=2):  # 10 .. 70
                clock.t = 15
                with instrument.span("koios.device_wait", what="wave"):
                    clock.t = 40                      # 15 .. 40
                with instrument.span("koios.verify"):  # 40 .. 60
                    clock.t = 45
                    with instrument.span("koios.device_wait",
                                         what="solver"):
                        clock.t = 50                  # 45 .. 50
                    clock.t = 60
                clock.t = 70
            clock.t = 100
    assert c["span_n:koios.step"] == 1
    assert c["span_n:koios.device_wait"] == 2
    assert c["span_ns:koios.step"] == 100
    assert c["self_ns:koios.step"] == 100 - 60
    assert c["span_ns:koios.wave"] == 60
    assert c["self_ns:koios.wave"] == 60 - 25 - 20
    assert c["span_ns:koios.verify"] == 20
    assert c["self_ns:koios.verify"] == 20 - 5
    assert c["span_ns:koios.device_wait"] == 25 + 5
    assert c["self_ns:koios.device_wait"] == 30
    # self times add up to the outermost span, exactly
    assert sum(v for k, v in c.items() if k.startswith("self_ns:")) == 100


def test_a_span_still_closes_and_counts_when_its_block_raises(clock):
    with instrument.counting() as c:
        with pytest.raises(RuntimeError):
            with instrument.span("koios.step"):
                clock.t = 7
                raise RuntimeError("boom")
        with instrument.span("koios.join"):
            clock.t = 9
    assert c["span_ns:koios.step"] == 7
    # the failed span left the stack: the next one is not its child
    assert c["self_ns:koios.step"] == 7
    assert c["span_ns:koios.join"] == 2


def test_nothing_is_recorded_outside_counting(monkeypatch):
    reads = []
    monkeypatch.setattr(instrument, "_clock",
                        lambda: reads.append(1) or 0)
    sp = instrument.span("koios.step", step=1)
    # no counter and no profiler: the shared no-op, no clock read
    assert sp is instrument.span("koios.join")
    with sp as inner:
        inner.annotate(wave=3)
        instrument.record("filter:candidates", 5)
    assert reads == []
    with instrument.counting() as c:
        pass
    with instrument.span("koios.step"):
        instrument.record("h2d:solver_dispatch")
    assert not c and reads == []


def test_span_keys_stay_out_of_the_transfer_totals(clock):
    with instrument.counting() as c:
        for name in instrument.SPANS:
            with instrument.span(name):
                clock.t += 3
        instrument.record("h2d:solver_dispatch")
    keys = [k for k in c if "koios." in k]
    assert len(keys) == 3 * len(instrument.SPANS)
    assert not [k for k in keys if k.startswith(("h2d:", "d2h:"))]
    assert instrument.totals(c)["total"] == 1


def _span_literals():
    pat = re.compile(r"""\bspan\(\s*["']([^"']+)["']""")
    found = {}
    for path in SRC.rglob("*.py"):
        for name in pat.findall(path.read_text()):
            found.setdefault(name, path.name)
    return found


def test_program_span_names_are_listed_and_apart_from_the_harness():
    used = _span_literals()
    assert set(used) == set(instrument.SPANS), used
    for name in instrument.SPANS:
        assert name.startswith("koios."), name
        assert name not in trace_reduce.HOST_SPANS
        assert name != trace_reduce.WINDOW


def test_engine_step_yields_every_span_and_the_filter_funnel(small_world):
    """One ``serve()`` on the fused schedule (interpret mode) under
    ``counting()``: every program span is entered, the self times nest
    inside the steps, and the filter counters equal the tiles'
    ``SearchStats`` (summed into each response by the merge).  No
    verification round runs in the wave, so the host continuation
    verifies."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret", wave_rounds=0)
    eng = RequestEngine(coll, sim, params, partitions=2, schedule="fused")
    assert eng.schedule == "fused"
    queries = sample_queries(coll, 4, seed=5)
    with instrument.counting() as c:
        out = eng.serve(queries)
    assert len(out) == len(queries) and all(r.served for r in out)
    for name in instrument.SPANS:
        assert c[f"span_n:{name}"] > 0, name
    selfs = sum(v for k, v in c.items() if k.startswith("self_ns:"))
    assert selfs <= c["span_ns:koios.step"]
    assert c["span_n:koios.step"] >= eng.counters.steps
    stats = [r.result.stats for r in out]
    for key, field in [("candidates", "candidates"),
                       ("pruned_refinement", "pruned_refinement"),
                       ("pruned_postprocess", "pruned_postprocess"),
                       ("no_em", "pruned_no_em"),
                       ("em_early", "pruned_em_early"),
                       ("em_full", "exact_matches")]:
        assert c[f"filter:{key}"] == sum(getattr(s, field) for s in stats)
    assert c["filter:candidates"] > 0 and c["filter:em_full"] > 0


@pytest.mark.parametrize("verifier", ["hungarian", "auction", "hybrid"])
def test_a_pool_round_builds_its_weights_on_the_device(small_world,
                                                       verifier):
    """One ``verify_requests`` round: every solver row's weights come from
    the device weight program (``verify:device_weight_rows`` equals the
    solver rows), and the only device-to-host copies are the solver's
    outputs: no similarity or weight block reaches the host."""
    coll, sim = small_world
    pool = VerifierPool(coll, sim, SearchParams(k=5, alpha=0.8,
                                                verify_batch=8,
                                                verifier=verifier))
    queries = sample_queries(coll, 3, seed=5)
    reqs = [VerifyRequest(q, np.arange(10 * i, 10 * i + 5 + 3 * i), th)
            for i, (q, th) in enumerate(zip(queries, (-np.inf, 1.0, 2.0)))]
    with instrument.counting() as c:
        outs = pool.verify_requests(reqs)
    rows = sum(len(r.ids) for r in reqs)
    assert c["verify:device_weight_rows"] == c["verify:solver_rows"]
    assert c["verify:device_weight_rows"] >= rows
    if verifier == "hungarian":
        assert c["verify:device_weight_rows"] == rows
    assert sum(o.n_full + o.n_early for o in outs) == rows
    assert "d2h:weights_materialize" not in c
    assert "h2d:pairwise_dispatch" not in c
    assert {k for k in c if k.startswith("d2h:")} == \
        {"d2h:solver_materialize"}
    assert c["span_n:koios.verify.weights"] == c["h2d:solver_dispatch"]
    assert c["span_n:koios.device_wait"] == c["h2d:solver_dispatch"]
    logical = sum(len(r.query) * int(coll.set_sizes[r.ids].sum())
                  for r in reqs)
    assert c["verify:solver_cells_logical"] >= logical
    if verifier == "hungarian":
        assert c["verify:solver_cells_logical"] == logical
    assert 0 < c["verify:solver_cells_logical"] \
        <= c["verify:solver_cells_padded"]


def test_a_hungarian_round_counts_its_solver_steps_in_the_solver_copy(
        small_world):
    """The exact solver's lockstep scan count comes back with its scores:
    ``verify:solver_steps`` is positive (rows without an edge at or
    above alpha are not augmented), one solver copy per dispatch and
    no other device-to-host record; the padded cells are the solver's
    (B, R, max(R, C)) rectangles."""
    coll, sim = small_world
    pool = VerifierPool(coll, sim, SearchParams(k=5, alpha=0.8,
                                                verify_batch=8))
    queries = sample_queries(coll, 3, seed=6)
    reqs = [VerifyRequest(q, np.arange(7 * i, 7 * i + 6), -np.inf)
            for i, q in enumerate(queries)]
    with instrument.counting() as c:
        pool.verify_requests(reqs)
    rows = sum(len(q) for q in queries)
    assert c["verify:solver_steps"] > 0
    assert c["verify:solver_steps"] <= rows * pool._c_pad
    assert {k for k in c if k.startswith(("d2h:", "h2d:"))} == \
        {"d2h:solver_materialize", "h2d:solver_dispatch"}
    assert c["d2h:solver_materialize"] == c["h2d:solver_dispatch"]
    assert c["span_n:koios.device_wait"] == c["h2d:solver_dispatch"]
    assert c["verify:solver_cells_padded"] % pool._c_pad == 0


def test_the_served_continuation_builds_every_weight_block_on_the_device(
        small_world):
    """The fused engine's host continuation (no device rounds, so the pool
    verifies everything): ``verify:device_weight_rows`` equals the solver
    rows and no weight block is copied to the host."""
    coll, sim = small_world
    params = SearchParams(k=5, alpha=0.8, chunk_size=64, verify_batch=8,
                          fused="interpret", wave_rounds=0)
    eng = RequestEngine(coll, sim, params, partitions=2, schedule="fused")
    with instrument.counting() as c:
        out = eng.serve(sample_queries(coll, 4, seed=5))
    assert all(r.served for r in out)
    assert c["verify:device_weight_rows"] > 0
    assert c["verify:device_weight_rows"] == c["verify:solver_rows"]
    assert not [k for k in c if "weights" in k and k.startswith("d2h:")]
    assert "h2d:pairwise_dispatch" not in c
