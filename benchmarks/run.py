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
                    choices=[None, "pruning", "response", "parameters",
                             "quality", "kernels", "roofline", "soak"])
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="run the fused-wave A/Bs off the chip, Pallas "
                         "kernels in interpret mode")
    args = ap.parse_args(argv)
    enable_compile_cache()
    fused = "interpret" if args.interpret else "auto"

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

    if want("response"):
        _banner("Table III: response time vs baselines")
        from . import response_time
        print("dataset,sim,koios_s,baseline_s,baseline+_s,speedup,"
              "em_koios,em_baseline,mem_mb")
        for r in response_time.run(n_queries=2):
            print(f"{r['dataset']},{r['sim']},{r['koios_s']:.2f},"
                  f"{r['baseline_s']:.2f},{r['baseline_plus_s']:.2f},"
                  f"{r['speedup']:.1f},{r['em_koios']:.0f},"
                  f"{r['em_baseline']:.0f},{r['mem_mb']:.1f}")
        _banner("Scale-out: overlapped scheduler vs sequential partitions")
        print("dataset,partitions,sequential_s,overlap_s,speedup,"
              "bound_raises,backward_raises")
        r = response_time.run_partition_ab(
            partitions=4, batch_size=4 if args.fast else 8)
        print(f"{r['dataset']},{r['partitions']},{r['sequential_s']:.4f},"
              f"{r['overlap_s']:.4f},{r['speedup']:.2f},"
              f"{r['bound_raises']},{r['backward_raises']}")
        _banner("Fused wave: on-device schedule vs host-driven overlap")
        print("dataset,partitions,overlap_s,fused_s,speedup,"
              "overlap_transfers,fused_transfers,result_hash")
        rf = response_time.run_fused_ab(
            partitions=4, batch_size=4 if args.fast else 8, fused=fused)
        print(f"{rf['dataset']},{rf['partitions']},{rf['overlap_s']:.4f},"
              f"{rf['fused_s']:.4f},{rf['speedup']:.2f},"
              f"{rf['overlap_transfers']},{rf['fused_transfers']},"
              f"{rf['result_hash']}")
        _banner("Request engine: continuous batching vs per-batch loop")
        print("dataset,partitions,batch_loop_s,engine_s,speedup,"
              "cache_hit_rate,mean_queue_depth")
        re_ = response_time.run_engine_ab(
            partitions=4, batch_size=4 if args.fast else 8,
            n_requests=8 if args.fast else 16,
            stagger_ms=10.0 if args.fast else 25.0)
        print(f"{re_['dataset']},{re_['partitions']},"
              f"{re_['batch_loop_s']:.4f},{re_['engine_s']:.4f},"
              f"{re_['speedup']:.2f},{re_['cache_hit_rate']:.2f},"
              f"{re_['mean_queue_depth']:.1f}")
        _banner("Sharded collection: N-shard resource vs 1-shard reference")
        print("dataset,shards,devices,one_shard_s,sharded_s,speedup,"
              "result_hash")
        rs = response_time.run_sharded_ab(
            shards=4, batch_size=4 if args.fast else 8, fused=fused)
        print(f"{rs['dataset']},{rs['shards']},{rs['devices']},"
              f"{rs['one_shard_s']:.4f},{rs['sharded_s']:.4f},"
              f"{rs['speedup']:.2f},{rs['result_hash']}")
        response_time.write_bench_json({
            "partition_ab": r, "fused_ab": rf, "engine_ab": re_,
            "sharded_ab": rs,
        }, "BENCH_response_time.json", "suite")
        if not args.fast:
            _banner("SilkMoth-mode (char n-gram similarity, §VIII-B)")
            for r in response_time.run(datasets=("opendata",),
                                       sim_kind="ngram",
                                       include_baseline=False):
                print(f"{r['dataset']},ngram,koios_s={r['koios_s']:.2f}")

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
