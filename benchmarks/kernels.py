"""Kernel microbenchmarks: us/call of the Pallas kernels in interpret mode
(``interpret=True`` on every call — a correctness-path timing, never a
device figure) and their jnp oracles.

The refinement-scan rows are the PR-5 tentpole's A/B: the serial
per-event admission loop vs the set-segmented parallel scan (lane-packed
levels), on a broad multi-set stream (the serving-typical shape, where
level widths are large and the segmented depth is a small fraction of
the chunk) AND on a skewed one-set-heavy stream (the worst case, where
one deep segment pins the sequential depth near the chunk length).  The
Pallas `refine_events` arm runs in interpret mode — dispatch-bound on
CPU; its TPU story is the VMEM-resident carry.

Rows are also written to ``BENCH_kernels.json`` (CI artifact) so the
kernel-level perf trajectory accumulates across commits; ``--json ''``
disables."""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import (auction_topk2, auction_topk2_ref, cosine_topk,
                           cosine_topk_ref, refine_events, ssd, ssd_ref)
from repro.runtime.compile_cache import enable_compile_cache

from .common import csv_line


def _time(fn, *args, reps=5):
    fn(*args)                     # compile/warm
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, out)
    return (time.time() - t0) / reps * 1e6


def _refinement_rows():
    """Serial per-event loop vs segmented scan vs Pallas-interpret on
    REAL bench-preset posting streams (the zipf posting skew is what the
    lane packing exploits — synthetic uniform streams misrepresent both
    layouts).  ``wdc`` is the deep-stream case the segmented scan wins
    outright; ``opendata`` is the skew-dominated small-stream case where
    one long per-set segment pins the sequential depth (the honest
    worst case)."""
    from repro.core import InvertedIndex, build_token_stream, \
        expand_to_events
    from repro.core.refinement import run_refinement
    from repro.core.token_stream import pack_events_segmented, pad_events
    from repro.data import sample_queries

    from .common import world

    rows = []
    for name in ("wdc", "opendata"):
        coll, sim = world(name)
        inv = InvertedIndex.build(coll)
        qs = sample_queries(coll, 4, seed=11)
        evs = [expand_to_events(build_token_stream(q, sim, 0.8), inv)
               for q in qs]
        i = int(np.argmax([len(e) for e in evs]))
        ev, q = evs[i], qs[i]
        nq, total_slots, sizes = len(q), coll.total_tokens, coll.set_sizes
        derived = f"{name} E={len(ev)} sets={coll.num_sets} chunk=256"
        for layout in ("serial", "segmented"):
            us = _time(lambda layout=layout: run_refinement(
                ev, sizes, nq, total_slots, 10, 0.8, 256, "sound",
                layout=layout), reps=20)
            rows.append((f"refine_scan_{layout}_{name}", us, derived))
        # Pallas kernel arm: admission of the packed chunks (interpret
        # mode — dispatch-bound on CPU; the TPU pitch is the
        # VMEM-resident carry)
        s3, q3, sl3, si3, _ = pack_events_segmented(*pad_events(ev, 256))
        from repro.core.refinement import refine_carry_init
        qw = max(1, -(-nq // 32))
        state = refine_carry_init(coll.num_sets, qw, total_slots)[:-1]

        def kernel_chain(state=state, s3=s3, q3=q3, sl3=sl3, si3=si3):
            st = state
            for c in range(s3.shape[0]):
                out = refine_events(st, s3[c], q3[c], sl3[c], si3[c],
                                    interpret=True)
                st = out[:5] + (st[5],) + out[5:]
            return st

        rows.append((f"refine_events_interp_{name}", _time(kernel_chain, reps=1),
                     derived + " (admission only)"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="perf-artifact path ('' disables)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    rng = np.random.default_rng(0)
    rows = []

    qe = rng.normal(size=(16, 64)).astype(np.float32)
    ev = rng.normal(size=(2048, 64)).astype(np.float32)
    qe /= np.linalg.norm(qe, axis=1, keepdims=True)
    ev /= np.linalg.norm(ev, axis=1, keepdims=True)
    rows.append(("cosine_topk_interp",
                 _time(lambda: cosine_topk(qe, ev, k=16, bv=256,
                                           interpret=True)),
                 "nq=16 nv=2048 d=64 k=16"))
    rows.append(("cosine_topk_ref",
                 _time(lambda: cosine_topk_ref(jnp.asarray(qe),
                                               jnp.asarray(ev), 16)),
                 "jnp oracle"))

    wm = rng.random((256, 512)).astype(np.float32)
    pr = rng.random(512).astype(np.float32)
    rows.append(("auction_topk2_interp",
                 _time(lambda: auction_topk2(wm, pr, bn=128,
                                             interpret=True)),
                 "n=256 m=512"))
    rows.append(("auction_topk2_ref",
                 _time(lambda: auction_topk2_ref(jnp.asarray(wm),
                                                 jnp.asarray(pr))),
                 "jnp oracle"))

    Bt, L, H, P, G, S = 1, 64, 4, 16, 1, 16
    x = rng.normal(size=(Bt, L, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(Bt, L, H)))).astype(np.float32)
    A = (-np.exp(rng.normal(size=H))).astype(np.float32)
    B = (rng.normal(size=(Bt, L, G, S)) / 4).astype(np.float32)
    C = (rng.normal(size=(Bt, L, G, S)) / 4).astype(np.float32)
    D = rng.normal(size=H).astype(np.float32)
    rows.append(("ssd_interp",
                 _time(lambda: ssd(x, dt, A, B, C, D, chunk=16,
                                   interpret=True)),
                 f"B={Bt} L={L} H={H} P={P} S={S}"))
    rows.append(("ssd_ref",
                 _time(lambda: ssd_ref(jnp.asarray(x[0]), jnp.asarray(dt[0]),
                                       jnp.asarray(A), jnp.asarray(B[0]),
                                       jnp.asarray(C[0]), jnp.asarray(D))),
                 "sequential oracle"))

    rows.extend(_refinement_rows())

    for name, us, derived in rows:
        print(csv_line(name, us, derived))

    if args.json:
        doc = {"benchmark": "kernels",
               "rows": [{"name": n, "us_per_call": us, "derived": d}
                        for n, us, d in rows]}
        serial = {n: us for n, us, _ in rows
                  if n.startswith("refine_scan_serial")}
        seg = {n: us for n, us, _ in rows
               if n.startswith("refine_scan_segmented")}
        doc["refine_speedup_wdc"] = (
            serial.get("refine_scan_serial_wdc", 0.0)
            / max(seg.get("refine_scan_segmented_wdc", 1.0), 1e-9))
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"[bench] wrote {args.json} ({len(rows)} rows)")
    return rows


if __name__ == "__main__":
    main()
