"""The harness end to end on the CPU: it refuses to run without a chip;
it finds configurations, mixes and metrics by name; and, driven past
its look for a chip at a tiny size, it reports ``correct`` true for the
program as it is and false when the timed path is broken underneath."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _paths
import run

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(cwd, timeout=300):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "twitter.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=ENV, capture_output=True, text=True, timeout=timeout)


def test_exits_nonzero_without_a_tpu():
    p = _run(_paths.ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(_paths.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_new_entries_are_found_by_name(tmp_path):
    """A config, a mix and a metric added as files plus entries."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "dummy-c.json").write_text(
        json.dumps({"name": "dummy-c", "corpus": {}}))
    (tmp_path / "bench" / "traffic" / "dummy_mix.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 1.0}))
    (tmp_path / "bench" / "metrics" / "dummy.metric.py").write_text(
        "def read(rec):\n    return 2.0 * rec['x']\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "run_seconds": 5,
        "configs": [{"name": "dummy-c", "file": "bench/configs/dummy-c.json"}],
        "workloads": [{"name": "dummy.cell", "config": "dummy-c",
                       "traffic": "dummy_mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "dummy.metric", "unit": "x",
                       "workloads": ["dummy.cell"]},
                      {"name": "other.metric", "unit": "x",
                       "workloads": ["other.cell"]}]}))
    cell = run.load_cell("dummy.cell", root=tmp_path)
    assert cell["config"]["name"] == "dummy-c"
    assert cell["mix"]["rate_per_s"] == 1.0
    assert [m["name"] for m in cell["per_layer"]] == ["dummy.metric"]
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s"]
    read = run.metric_reader("dummy.metric", bench=tmp_path / "bench")
    assert read({"x": 3.0}) == 6.0
    with pytest.raises(KeyError):
        run.load_cell("missing.cell", root=tmp_path)


def test_every_benchmark_entry_has_its_files():
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (_paths.ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (_paths.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        cell = run.load_cell(w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


# ------------------------------------------------ a whole run, tiny, CPU
def _tiny_cell(loop):
    cfg = json.loads((_paths.DATA / "tiny-config.json").read_text())
    mix = {"loop": loop, "rate_per_s": 12.0, "clients": 4,
           "max_requests": 1500, "queries": "fresh",
           "warmup": {"cohorts": 2, "seconds": 0.5, "cover": 1.0},
           "check": 8}
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    return {"name": "tiny", "cell": {"chips": 1}, "config": cfg, "mix": mix,
            "end_to_end": bench["end_to_end"], "per_layer": [],
            "run_seconds": 2}


def _break(monkeypatch, fault):
    """Break the timed path underneath the harness."""
    from repro.runtime import engine

    merge = engine.merge_topk

    def altered(parts, k):
        res = merge(parts, k)
        ids, lb = res.ids.copy(), res.lb.copy()
        if fault == "altered_id" and len(ids) > 1:
            ids[-1] = (int(ids[-1]) + 97) % 1500
        if fault == "altered_score" and len(lb):
            lb[0] += np.float32(1e-3)
        return dataclasses.replace(res, ids=ids, lb=lb)

    monkeypatch.setattr(engine, "merge_topk", altered)
    if fault == "dropped":
        step = engine.RequestEngine.step

        def lossy(self):
            return [r for r in step(self) if r.rid % 3 != 1]

        monkeypatch.setattr(engine.RequestEngine, "step", lossy)


@pytest.mark.parametrize("loop,fault", [
    ("open", None), ("closed", None), ("open", "altered_id"),
    ("open", "altered_score"), ("closed", "dropped")])
def test_run_is_correct_only_when_the_answers_are(monkeypatch, loop, fault):
    _break(monkeypatch, fault)
    line = run.run_cell(_tiny_cell(loop), seed=2**31 + 5, seconds=1.5,
                        trace=False, require_tpu=False,
                        params_override={"fused": "interpret"},
                        log=lambda *a: None)
    assert line["attempted"] > 0
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) >= {"setup_s"}
    assert line["correct"] is (fault is None), line["check"]
