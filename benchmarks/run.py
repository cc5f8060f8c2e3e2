"""Benchmark entrypoint: one function per paper table/figure.

``python -m benchmarks.run`` runs everything at CPU-feasible scale and
prints ``name,us_per_call,derived`` CSV lines plus the per-table reports.
``--only <name>`` runs a single benchmark; ``--fast`` trims query counts."""
from __future__ import annotations

import argparse
import time

from repro.runtime.compile_cache import enable_compile_cache


def _banner(name):
    print(f"\n===== {name} " + "=" * max(0, 60 - len(name)), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "pruning", "parameters", "quality",
                             "kernels", "roofline", "soak"])
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    t0 = time.time()
    want = lambda n: args.only in (None, n)   # noqa: E731

    if want("kernels"):
        _banner("kernel microbench (us/call)")
        from . import kernels
        kernels.main([])

    if want("pruning"):
        _banner("Table II: filter pruning power")
        from . import pruning_power
        print("dataset,interval,candidates,iUB%,No-EM,EM-early,EM,verified%")
        for r in pruning_power.run(n_queries=2):
            print(f"{r['dataset']},{r['interval']},{r['candidates']:.0f},"
                  f"{r['refine_prune_pct']:.1f},{r['no_em']:.1f},"
                  f"{r['em_early']:.1f},{r['em_full']:.1f},"
                  f"{r['verified_pct']:.2f}")
        if not args.fast:
            _banner("Tables IV/V: pruning by query cardinality (opendata)")
            for r in pruning_power.run(datasets=("opendata",),
                                       by_cardinality=True, n_queries=2):
                print(f"{r['dataset']},{r['interval']},"
                      f"cand={r['candidates']:.0f},"
                      f"iUB%={r['refine_prune_pct']:.1f},"
                      f"verified%={r['verified_pct']:.2f}")

    if want("soak"):
        _banner("Fault-injected soak: failover + deadline shedding")
        from . import soak
        soak.main(["--fast"] if args.fast else [])

    if want("parameters"):
        _banner("Fig 7: parameter analysis")
        from . import parameters
        parameters.main()

    if want("quality"):
        _banner("Fig 8: semantic vs vanilla quality")
        from . import quality
        for r in quality.run(datasets=("dblp",), n_queries=2):
            print(f"{r['dataset']},{r['query']},{r['|Q|']},"
                  f"{r['kth_semantic']:.2f},{r['kth_vanilla']:.2f},"
                  f"{r['intersection']},{r['semantic_gain']:.2f}")

    if want("roofline"):
        _banner("Roofline table (from dry-run artifacts)")
        from . import roofline
        try:
            roofline.main()
        except Exception as e:                      # noqa: BLE001
            print(f"(no dry-run artifacts yet: {e})")

    print(f"\ntotal bench time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
