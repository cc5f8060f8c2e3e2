"""The control of the correctness check: the reference put in the
program's place, with its similarities computed one precision below the
configuration's float32: bfloat16 operands, one pass, float32 sums (what
a float32 matmul at default precision runs on a TPU).  Its answers have
to come out as not correct.

    python3 bench/control.py --workload twitter.closed --seeds 1,2,3

For each seed it takes the sets of the schedule's first ``check``
requests (those a window answers, see ``loadgen.py``), answers each with
the reference's own top-k over the bfloat16 similarities, and compares
those answers with the float64 reference exactly as a run does.  On a
TPU the matmul runs on the chip; elsewhere it is emulated exactly on the
host.

It prints one line per seed and, last, a JSON summary with the smallest
score gap over the seeds: the upper reading of the score limit.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import loadgen                    # noqa: E402
import reference                  # noqa: E402
import run                        # noqa: E402


def device_sims():
    """Similarities from a matmul of bfloat16 operands on the default JAX
    device, summed in float32."""
    import jax.numpy as jnp

    cache = {}

    def sims(e64, query):
        if cache.get("of") is not e64:       # a new seed's table
            cache["of"] = e64
            cache["e"] = jnp.asarray(e64.astype(np.float32), jnp.bfloat16)
        e = cache["e"]
        q = np.asarray(query, np.int64)
        s = jnp.dot(e[q], e.T, preferred_element_type=jnp.float32)
        s = np.clip(np.asarray(s, np.float64), 0.0, 1.0)
        s[np.arange(len(q)), q] = 1.0
        return s

    return sims


def checked_sets(cell: dict, world, seed: int, seconds: float) -> list:
    """The sets of the schedule's first ``check`` requests."""
    sched = loadgen.schedule(cell["mix"], world.sizes, seed, seconds)
    return [int(s) for s in sched.sets[:int(cell["mix"]["check"])]]


def control_gap(cell: dict, seed: int, sims_fn, seconds: float) -> dict:
    world = run.World(cell["config"], seed)
    s = cell["config"]["search"]
    tol = cell["config"]["limits"]["score_gap"]
    e64 = reference.normalize(world.emb)
    gap, errors = 0.0, 0
    sets = checked_sets(cell, world, seed, seconds)
    for sid in sets:
        q = world.query(sid)
        ids, scores = reference.control_topk(
            sims_fn, e64, world.indptr, world.tokens, q, s["alpha"], s["k"])
        ref = reference.Reference(world.indptr, world.tokens,
                                  reference.sims_f64(e64, q), s["alpha"])
        g, e, _ = reference.compare(ids, scores, ref, s["k"], tol)
        gap, errors = max(gap, g), errors + e
    return {"seed": seed, "requests": len(sets), "score_gap": float(gap),
            "id_errors": int(errors), "limit": tol,
            "correct": bool(gap <= tol and errors == 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    seconds = cell["run_seconds"]
    import jax

    on_chip = jax.devices()[0].platform == "tpu"
    sims_fn = device_sims() if on_chip else reference.sims_bf16
    rows = []
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.monotonic()
        row = control_gap(cell, seed, sims_fn, seconds)
        row["seconds"] = time.monotonic() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "device": jax.devices()[0].device_kind if on_chip else "host",
        "min_score_gap": min(r["score_gap"] for r in rows),
        "all_incorrect": not any(r["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
