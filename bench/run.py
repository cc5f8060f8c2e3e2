"""KOIOS benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload twitter.closed --seed 1 --seconds 30 \\
        --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``: the corpus, the embedding width, the
search settings and the limits of the correctness check) and a traffic
mix (``bench/traffic/<traffic>.json``: parameters read by
``loadgen.py``).  Every metric, end to end or per layer, is computed by
its own reader, ``bench/metrics/<name>.py``, found by the metric's name.
A new configuration, mix or metric is a new file and a new entry in
``BENCHMARK.json``.

A run:

1. builds the corpus from the configuration's fixed ``corpus_seed`` and
   the embedding values from ``--seed`` (``corpus.py``);
2. builds ``ShardedCollection.build(coll, partitions)`` and a fused
   ``RequestEngine``, and warms it with the mix's warm-up (closed loop:
   the window's own cohorts, in order; open loop: doubling cohorts,
   then a replay of the mix from a fixed seed; see ``loadgen.py``);
3. serves the window, ``--seconds`` long, from a second engine over the
   same collection (empty stream cache; a ``zipf`` mix has its pool's
   streams put in it first), driven by ``engine.submit``/``engine.step``
   from the schedule; requests still open at the close are waited for,
   up to a minute, and their latency counts the wait;
4. reads the device's peak memory, frees the engines, and compares a
   sample of the answers, drawn from the seed, with the float64
   reference (``reference.py``);
5. prints the compared numbers and their limits as the last lines of
   standard error, and one JSON line as the last line of standard
   output.

With ``--trace 1`` the window runs under the JAX profiler and the line
holds the per-layer metrics, the device's busy and window seconds, and
a breakdown of device time and idle gaps.

The run exits 2, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is
kept at ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

T_START = time.monotonic()

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
GRACE_S = 60.0                   # wait past the close for open requests
TRACE_S = 8.0                    # traced part of a --trace 1 window

sys.path.insert(0, str(BENCH))

import corpus                     # noqa: E402
import loadgen                    # noqa: E402
import reference                  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ definitions
def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    mix and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {"name": name, "cell": cell, "config": config, "mix": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "run_seconds": bench["run_seconds"]}


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """``read(record)`` of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ world
class World:
    """The deployment's data: corpus (fixed by ``corpus_seed``) and the
    embedding table (values from the run's seed)."""

    def __init__(self, config: dict, seed: int):
        c, e = config["corpus"], config["embedding"]
        self.indptr, self.tokens = corpus.make_corpus(
            c["num_sets"], c["vocab"], c["avg_size"], c["max_size"],
            c["zipf_a"], c["corpus_seed"])
        self.sizes = self.indptr[1:] - self.indptr[:-1]
        self.vocab = c["vocab"]
        self.emb = corpus.make_embeddings(
            c["vocab"], e["dim"], structure_seed=c["corpus_seed"],
            value_seed=seed, cluster_size=e["cluster_size"],
            intra_cos=e["intra_cos"])

    def query(self, sid: int):
        return self.tokens[self.indptr[sid]:self.indptr[sid + 1]]


# ----------------------------------------------------------------- engine
def search_params(config: dict, **override):
    from repro.core import SearchParams

    s = config["search"]
    return SearchParams(k=s["k"], alpha=s["alpha"], verifier=s["verifier"],
                        **override)


def make_engine(collection, provider, params, config: dict):
    from repro.runtime.engine import RequestEngine

    s = config["search"]
    eng = RequestEngine(None, provider, params, collection=collection,
                        schedule="fused",
                        stream_cache_bytes=s["stream_cache_bytes"],
                        max_wave_requests=s["max_wave_requests"])
    if eng.schedule != "fused":
        raise RuntimeError(f"fused schedule resolved to {eng.schedule!r}")
    return eng


class Compiles:
    """Programs JAX compiled, or loaded from its persistent cache, from a
    ``jax.monitoring`` listener: one entry (time, seconds, name) per
    backend compile, and the seconds of every phase of compiling
    (tracing, lowering, backend compile)."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    PHASES = (BACKEND, "/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self, clock):
        import jax

        self.clock, self.at, self.total = clock, [], 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.PHASES:
            self.total += float(duration)
        if event == self.BACKEND:
            self.at.append((self.clock(), float(duration),
                            kw.get("fun_name", "")))

    def between(self, lo: float, hi: float) -> list:
        return [c for c in self.at if lo <= c[0] <= hi]

    def seconds(self) -> float:
        return self.total


def drive(engine, world: World, sched, seconds: float, clock,
          annotate=None, stop_trace=None,
          grace_s: float = GRACE_S) -> dict:
    """Serve ``sched`` for ``seconds``; returns the window's request
    records and its open/close times.  Requests due in the window and
    still open at the close are waited for up to ``grace_s``.  With
    ``stop_trace`` the traced part (annotated ``bench.window``) ends at
    the first step boundary ``TRACE_S`` after the open, where
    ``stop_trace`` is called: a profiler trace of a whole window takes
    minutes to collect."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    recs: dict = {}
    spans = []                 # (start, end, requests) of every step
    traced = contextlib.ExitStack()
    t_traced = []              # end of the traced part

    def submit(i: int, due: float) -> None:
        rid = engine.submit(world.query(int(sched.sets[i])), arrival=due)
        recs[rid] = {"rid": rid, "i": i, "set": int(sched.sets[i]),
                     "due": due, "done": None, "ok": False,
                     "sub_step": len(spans)}

    def end_trace() -> None:
        if stop_trace is not None and not t_traced:
            traced.close()
            t_traced.append(clock())
            stop_trace()

    def step() -> list:
        t0, n0 = clock(), len(engine.counters.wave_sizes)
        out = finish(engine.step())
        t1 = clock()
        ws = engine.counters.wave_sizes
        spans.append((t0, t1, ws[-1] if len(ws) > n0 else 0))
        if t1 >= t_open + TRACE_S:
            end_trace()
        return out

    def finish(responses) -> list:
        for r in responses:
            rec = recs.get(r.rid)
            if rec is None:
                continue
            rec.update(done=rec["due"] + r.latency_s, ok=r.status == "ok",
                       queue_s=r.queue_s, stream_hit=r.stream_hit,
                       ids=r.result.ids.tolist(),
                       scores=r.result.lb.tolist())
        return responses

    t_open = clock()
    t_close = t_open + seconds
    traced.enter_context(ann("bench.window"))
    if sched.loop == "open":
        due = t_open + sched.offsets
        i, n = 0, len(due)
        while clock() < t_close:
            now = clock()
            if i < n and due[i] <= now:
                with ann("engine.submit"):
                    while i < n and due[i] <= now:
                        submit(i, float(due[i]))
                        i += 1
            if engine.pending():
                with ann("engine.step"):
                    step()
            else:
                nxt = min(due[i] if i < n else t_close, t_close)
                with ann("idle.wait_arrival"):
                    time.sleep(max(0.0, nxt - clock()))
        while i < n and due[i] < t_close:      # due, not yet sent
            submit(i, float(due[i]))
            i += 1
    else:
        nxt = 0
        with ann("engine.submit"):
            for _ in range(sched.clients):
                submit(nxt, clock())
                nxt += 1
        while clock() < t_close:
            with ann("engine.step"):
                out = step()
            if out and clock() < t_close:
                with ann("engine.submit"):
                    for _ in out:
                        if nxt >= len(sched.sets):
                            raise RuntimeError(
                                "the closed loop ran out of requests: "
                                "raise max_requests in the mix")
                        submit(nxt, clock())
                        nxt += 1
    end_trace()
    while engine.pending() and clock() < t_close + grace_s:
        step()
    # share of each request's work done inside the window: its engine
    # steps (one wave per shard) before the close, the step running at
    # the close by the part of it that ran before
    waves = len(engine.partitions)
    done = [min(max((t_close - s) / (e - s), 0.0), 1.0) if e > s else 1.0
            for s, e, _ in spans]
    for rec in recs.values():
        if rec["ok"] and rec["done"] <= t_close:
            rec["progress"] = 1.0
        else:
            mine = done[rec["sub_step"]:rec["sub_step"] + waves]
            rec["progress"] = sum(mine) / waves
    # requests' worth of work in the traced part
    t_end = t_traced[0] if t_traced else t_open
    work = sum(n / waves * min(max((t_end - s) / (e - s), 0.0), 1.0)
               for s, e, n in spans if e > s)
    return {"records": list(recs.values()), "t_open": t_open,
            "t_close": t_close, "t_traced": t_end, "traced_work": work}


def due_records(win: dict) -> list:
    """The requests the window is judged on: open loop, those due in
    it; closed loop, those sent in it."""
    return [r for r in win["records"] if r["due"] <= win["t_close"]]


# ------------------------------------------------------------------ check
def check_sample(records, n: int, seed: int) -> list:
    """Up to ``n`` answered records drawn from the seed, the one with
    the largest query among them."""
    import numpy as np

    ok = [r for r in records if r["ok"]]
    if not ok:
        return []
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 9])
    big = max(ok, key=lambda r: (r["qlen"], -r["rid"]))
    rest = [r for r in ok if r is not big]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [big] + [rest[int(j)] for j in sorted(pick)]


def check(world: World, config: dict, sample) -> dict:
    """Compare ``sample``'s answers with the float64 reference; returns
    the compared numbers."""
    e64 = reference.normalize(world.emb)
    s = config["search"]
    tol = config["limits"]["score_gap"]
    gap, errors, msgs = 0.0, 0, []
    for r in sample:
        ref = reference.Reference(world.indptr, world.tokens,
                                  reference.sims_f64(e64,
                                                     world.query(r["set"])),
                                  s["alpha"])
        g, e, m = reference.compare(r["ids"], r["scores"], ref, s["k"], tol)
        gap, errors = max(gap, g), errors + e
        msgs += [f"request {r['rid']} (set {r['set']}): {x}" for x in m]
    return {"score_gap": gap, "id_errors": errors, "messages": msgs}


# -------------------------------------------------------------------- run
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, params_override=None,
             t_start: float = T_START, log=print) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    devices = jax.devices()
    chips = int(cell["cell"]["chips"])
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.data import EmbeddingTableProvider
    from repro.runtime import instrument
    from repro.runtime.collection import ShardedCollection

    clock = time.monotonic
    compiles = Compiles(clock)
    config, mix = cell["config"], cell["mix"]
    world = World(config, seed)
    coll = _set_collection(world)
    collection = ShardedCollection.build(coll,
                                         config["search"]["partitions"])
    provider = EmbeddingTableProvider(world.emb)
    params = search_params(config, **(params_override or {}))

    # ---- set-up: warm the cell's shapes
    sched = loadgen.schedule(mix, world.sizes, seed, seconds)
    warm = make_engine(collection, provider, params, config)
    if mix["loop"] == "closed":
        # serve the window's own cohorts, in order, until they have taken
        # (compiles aside) warmup["cover"] times the window: the window
        # then reaches no cohort whose programs are not compiled
        served, n_warm = 0.0, 0
        for cohort in loadgen.closed_cohorts(sched, len(sched.sets)):
            if served >= float(mix["warmup"]["cover"]) * seconds:
                break
            t0, c0 = clock(), compiles.seconds()
            warm.serve([world.query(int(i)) for i in cohort])
            served += (clock() - t0) - (compiles.seconds() - c0)
            n_warm += 1
        log(f"[setup] warmed {n_warm} cohorts of {sched.clients}, "
            f"{served:.1f}s of serving without compiles")
    else:
        cohort = loadgen.warmup_cohort(mix, world.sizes)
        warm.warmup([world.query(int(i)) for i in cohort])
        drive(warm, world, loadgen.warmup_schedule(mix, world.sizes),
              float(mix["warmup"]["seconds"]), clock)
    eng = make_engine(collection, provider, params, config)
    pool = loadgen.pool_sets(mix, world.sizes, seed)
    if len(pool):
        from repro.core.token_stream import build_token_stream_batch_cached
        build_token_stream_batch_cached(
            [world.query(int(i)) for i in pool], provider, params.alpha,
            eng.stream_cache)
    log(f"[setup] corpus {coll.num_sets} sets, {coll.total_tokens} slots, "
        f"{len(collection.shards)} shards; set-up compiles "
        f"{len(compiles.at)}")

    # ---- window
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    annotate = jax.profiler.TraceAnnotation if trace else None
    if trace:
        # no Python tracer and host events at level 1 (the harness's
        # annotations): the trace of a 30 s window stays small
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = clock() - t_start
    def stop_trace():
        t_stop = clock()
        jax.profiler.stop_trace()
        log(f"[trace] stopped in {clock() - t_stop:.1f}s")

    with instrument.counting() as counts:
        win = drive(eng, world, sched, seconds, clock, annotate=annotate,
                    stop_trace=stop_trace if trace else None)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:chips])
    records = due_records(win)
    for r in records:
        r["qlen"] = int(world.sizes[r["set"]])
    rec = {
        "records": records, "t_open": win["t_open"],
        "t_close": win["t_close"], "seconds": seconds, "setup_s": setup_s,
        "counts": dict(counts), "wave_sizes": list(eng.counters.wave_sizes),
        "window_compiles": len(compiles.between(win["t_open"],
                                                win["t_close"])),
        "traced_work": win["traced_work"],
        "mix": mix,
    }
    late = compiles.between(win["t_open"], clock())
    log(f"[window] {len(records)} requests due, "
        f"{sum(r['ok'] for r in records)} answered ok, "
        f"{rec['window_compiles']} compiles in the window "
        f"({len(late)} with the wait after it: "
        f"{sorted(set(c[2] for c in late))}), "
        f"{len(eng.counters.wave_sizes)} steps, "
        f"scheduler {eng.summary()['scheduler']}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": peak}
    del warm, eng, collection, provider
    if trace:
        import trace_reduce

        t_read = clock()
        red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"[trace] read in {clock() - t_read:.1f}s")
        rec["device"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    # ---- correctness, after the window and with the engines freed
    t_check = clock()
    sample = check_sample(records, int(mix["check"]), seed)
    verdict = check(world, config, sample)
    unanswered = sum(1 for r in records if not r["ok"])
    lim = config["limits"]
    numbers = {
        "score_gap": (verdict["score_gap"], lim["score_gap"]),
        "id_errors": (verdict["id_errors"], 0),
        "unanswered": (unanswered, 0),
    }
    correct = (bool(sample) and all(v <= l for v, l in numbers.values()))
    log(f"[check] {len(sample)} answers compared with the float64 "
        f"reference in {clock() - t_check:.1f}s")
    for m in verdict["messages"][:20]:
        log(f"[check] {m}")

    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": correct, "attempted": len(records),
            "failed": unanswered, "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": rec["device"]["device_ops"],
                             "idle_gaps": rec["device"]["idle_gaps"]}
    line["check"] = {k: {"value": v, "limit": l}
                     for k, (v, l) in numbers.items()}
    return line


def _set_collection(world: World):
    from repro.core.types import SetCollection

    coll = SetCollection(set_indptr=world.indptr, set_tokens=world.tokens,
                         vocab_size=world.vocab)
    coll.validate()
    return coll


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    # the compile cache lives in the checkout, at a fixed path: the
    # program's own cache helper takes it from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        log=log)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for k, v in line["check"].items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
