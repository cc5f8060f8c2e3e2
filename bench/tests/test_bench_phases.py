"""Idle gaps named by the program's spans (``phases.py``), on handmade
planes and on a few engine steps traced on a TPU v5e chip; and the
reduction of a trace without program spans, fixed to its bytes."""
import json

import pytest

import _paths
import phases
import trace_reduce as tr

MS = 1_000_000


def test_trace_without_program_spans_reduces_to_its_recorded_bytes():
    """``tiny.reduce.json`` is ``trace_reduce``'s reduction of
    ``tiny.xplane.pb``; the split by phase leaves its gaps as they are."""
    path = str(_paths.DATA / "tiny.xplane.pb")
    planes = tr.load(path)
    want = (_paths.DATA / "tiny.reduce.json").read_text().strip()
    out = tr.reduce(planes)
    assert json.dumps(out) == want
    assert phases.load_program(path) == []
    assert phases.idle_by_phase(planes, []) == out["idle_gaps"]


def _planes():
    """A step [0, 100] ms with device work at [10, 20] and [70, 80]; the
    program's spans nest inside it, and the gap [20, 70] straddles the
    end of ``koios.device_wait`` and the start of ``koios.verify.pack``."""
    planes = {
        "device": {"/device:TPU:0": [("jit_fn(1)", 10 * MS, 20 * MS),
                                     ("jit__hungarian_padded(2)",
                                      70 * MS, 80 * MS)]},
        "host": [("bench.window", 0, 100 * MS),
                 ("engine.step", 0, 100 * MS)]}
    program = [("koios.step", 0, 100 * MS),
               ("koios.wave", 2 * MS, 95 * MS),
               ("koios.wave.launch", 2 * MS, 8 * MS),
               ("koios.device_wait", 8 * MS, 30 * MS),
               ("koios.verify", 30 * MS, 90 * MS),
               ("koios.verify.pack", 40 * MS, 60 * MS),
               ("koios.verify.solve", 60 * MS, 85 * MS),
               ("koios.device_wait", 65 * MS, 82 * MS)]
    return planes, program


def test_step_gaps_split_by_the_innermost_program_span():
    planes, program = _planes()
    gaps = dict(phases.idle_by_phase(planes, program))
    # [0, 2] step only; [2, 8] launch, [8, 10] wait; [20, 30] wait,
    # [30, 40] verify, [40, 60] pack, [60, 65] solve, [65, 70] wait;
    # [80, 82] wait, [82, 85] solve, [85, 90] verify, [90, 95] wave,
    # [95, 100] step only
    assert gaps == pytest.approx({
        "engine.step": 0.002 + 0.005,
        "koios.wave.launch": 0.006,
        "koios.device_wait": 0.002 + 0.010 + 0.005 + 0.002,
        "koios.verify": 0.010 + 0.005,
        "koios.verify.pack": 0.020,
        "koios.verify.solve": 0.005 + 0.003,
        "koios.wave": 0.005})
    out = tr.reduce(planes)
    assert dict(out["idle_gaps"]) == pytest.approx({"engine.step": 0.08})
    assert sum(gaps.values()) == pytest.approx(0.1 - out["busy_s"])


def test_program_spans_name_only_gaps_inside_engine_step():
    planes, program = _planes()
    planes["host"] = [("bench.window", 0, 100 * MS),
                      ("engine.submit", 0, 100 * MS)]
    gaps = dict(phases.idle_by_phase(planes, program))
    assert gaps == pytest.approx({"engine.submit": 0.08})


def test_recorded_tpu_trace_with_program_spans():
    """Four engine steps of the tiny configuration on the fused schedule,
    traced on one TPU v5e (kept: the device's ``XLA Modules`` line and
    the host's Python thread): the spans nest as the profiler recorded
    them, and nearly all the idle time inside ``engine.step`` is named by
    a program phase."""
    from jax.profiler import ProfileData
    from repro.runtime.instrument import SPANS

    path = str(_paths.DATA / "tiny-spans.xplane.pb")
    planes, program = tr.load(path), phases.load_program(path)
    assert {n for n, _, _ in program} == set(SPANS)
    steps = [(s, e) for n, s, e in program if n == phases.PROGRAM_STEP]
    host = [(s, e) for n, s, e in planes["host"] if n == phases.STEP]
    assert len(steps) == len(host) == 4
    for n, s, e in program:
        assert any(a <= s and e <= b for a, b in steps), n
    waves = [(s, e) for n, s, e in program if n == "koios.wave"]
    for n, s, e in program:
        if n.startswith(("koios.wave.", "koios.resume", "koios.finish")):
            assert any(a <= s and e <= b for a, b in waves), n

    gaps = dict(phases.idle_by_phase(planes, program))
    bare = dict(tr.reduce(planes, top=100)["idle_gaps"])
    in_step = sum(v for k, v in gaps.items()
                  if k.startswith(phases.PROGRAM) or k == phases.STEP)
    assert in_step == pytest.approx(bare[phases.STEP])
    assert gaps[phases.STEP] < 0.1 * in_step

    attrs = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(phases.PROGRAM):
                    attrs.setdefault(ev.name, set()).update(
                        k for k, _ in ev.stats)
    assert attrs["koios.step"] == {"step", "wave"}
    assert attrs["koios.wave"] == {"shard", "B"}
    assert attrs["koios.device_wait"] == {"what"}
