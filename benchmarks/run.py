"""Benchmark harness entry point — one registry entry per paper
figure/table or perf artifact.

  fig6  MD&A (continuous y): 4 algorithms × (time, test MSE)     [Fig. 6]
  fig7  IMDB (binary y): 4 algorithms × (time, test accuracy)    [Fig. 7]
  kernels  per-kernel µs/call
  roofline  aggregated dry-run roofline table (if artifacts exist)
  opt-in extras (--only): ablation, slda_predict, slda_train,
  slda_parallel, slda_ragged, slda_robust, slda_elastic, slda_serving,
  slda_serving_robust — the sLDA perf suites (quick shapes
  unless --full; headline A/B rows printed; run each bench module's
  own __main__ to write the JSON artifacts).

Every sLDA bench routes through the unified execution-plan entry
points (`core.plan.build_schedule` + the plan-driven `run_*`
orchestrators — DESIGN.md §Execution-plan), so a benched configuration
is exactly a dispatch-matrix cell; `python -m repro.launch.dryrun
--slda-plan` prints the plan a given config resolves to before paying
for a run.

Prints ``name,us_per_call,derived`` CSV rows plus per-figure detail.
Use --full for the paper-scale corpora (minutes on CPU).
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache


def _bench_fig6(args):
    from . import fig6_mdna
    scale = 1.0 if args.full else 0.1
    for r in fig6_mdna.run(scale=scale):
        print(f"fig6_{r['algorithm']},{r['wall_s'] * 1e6:.0f},"
              f"mse={r['test_mse']};modeled_s={r['modeled_s']}")


def _bench_fig7(args):
    from . import fig7_imdb
    scale = 1.0 if args.full else 0.02
    for r in fig7_imdb.run(scale=scale):
        print(f"fig7_{r['algorithm']},{r['wall_s'] * 1e6:.0f},"
              f"acc={r['test_acc']};modeled_s={r['modeled_s']}")


def _bench_ablation(args):
    # beyond-paper: quality vs chain count (slow — opt-in)
    from . import ablation_chains
    for r in ablation_chains.run():
        print(f"ablation_m{r['m']}_{r['rule']},0,mse={r['mse']}")


def _bench_kernels(args):
    from . import kernels_bench
    for r in kernels_bench.run():
        print(f"kernel_{r['name']},{r['us_per_call']},{r['derived']}")


def _bench_slda_predict(args):
    # end-to-end before/after for the fused prediction path (slow —
    # trains 8 chains twice; opt-in).  `python -m
    # benchmarks.bench_slda_predict` writes the JSON artifact.
    from . import bench_slda_predict
    payload = bench_slda_predict.run(scale=1.0 if args.full else 0.25)
    r = payload["results"]
    for k in ("weighted_average_seed_s", "weighted_average_fused_s"):
        print(f"slda_predict_{k},{r[k] * 1e6:.0f},"
              f"speedup={r['weighted_average_speedup']}x")


def _bench_slda_train(args):
    from . import bench_slda_train
    r = bench_slda_train.run(scale=1.0 if args.full else 0.25,
                             reps=5 if args.full else 1)["results"]
    print(f"slda_train_chain,{r['train_chain_fused_s'] * 1e6:.0f},"
          f"speedup={r['train_chain_speedup']}x")


def _bench_slda_parallel(args):
    from . import bench_slda_parallel
    r = bench_slda_parallel.run(quick=not args.full)["results"]
    print(f"slda_parallel_weighted,"
          f"{r['weighted_m8_batched_s'] * 1e6:.0f},"
          f"speedup={r['weighted_m8_speedup']}x;"
          f"mse_guard_ok={r['mse_guard_ok']}")


def _bench_slda_ragged(args):
    from . import bench_slda_ragged
    payload = bench_slda_ragged.run(quick=not args.full)
    r, m = payload["results"], payload["results"]["chains"]
    print(f"slda_ragged_weighted,"
          f"{r[f'weighted_m{m}_bucketed_s'] * 1e6:.0f},"
          f"speedup={r[f'weighted_m{m}_speedup']}x;"
          f"padding={r['padding_frac']};mse_guard_ok={r['mse_guard_ok']}")


def _bench_slda_robust(args):
    from . import bench_slda_robust
    r = bench_slda_robust.run(quick=not args.full)["results"]
    print(f"slda_robust_checks_on,{r['checks_on_s'] * 1e6:.0f},"
          f"overhead={r['health_check_overhead_frac']};"
          f"overhead_ok={r['health_check_overhead_ok']};"
          f"degraded_mse_guard_ok={r['degraded_mse_guard_ok']}")


def _bench_slda_elastic(args):
    from . import bench_slda_elastic
    r = bench_slda_elastic.run(quick=not args.full)["results"]
    print(f"slda_elastic_async_ckpt,{r['async_ckpt_s'] * 1e6:.0f},"
          f"async_vs_sync={r['async_vs_sync_frac']};"
          f"async_ok={r['async_ckpt_overhead_ok']};"
          f"kill_bitwise_ok={r['kill_device_survivors_bitwise_ok']};"
          f"retrace0_ok={r['zero_retraces_across_repack_ok']};"
          f"resume_bitwise_ok={r['preempt_resume_bitwise_ok']};"
          f"rounds_lost={r['preempt_rounds_lost']};"
          f"degraded_mse_guard_ok={r['degraded_mse_guard_ok']}")


def _bench_slda_serving(args):
    from . import bench_slda_serving
    r = bench_slda_serving.run(quick=not args.full)["results"]
    print(f"slda_serving_p50,{r['latency_p50_ms'] * 1e3:.0f},"
          f"p99_ms={r['latency_p99_ms']};"
          f"docs_per_s={r['throughput_docs_per_s']};"
          f"retraces={r['steady_state_retraces']};"
          f"cache_speedup={r['plan_cache_speedup']}x;"
          f"exact_match_ok={r['exact_match_ok']}")


def _bench_slda_serving_robust(args):
    from . import bench_slda_serving_robust
    r = bench_slda_serving_robust.run(quick=not args.full)["results"]
    print(f"slda_serving_robust_p99,"
          f"{r['burst_with_admission']['latency_p99_s'] * 1e6:.0f},"
          f"p99_bounded_ok={r['p99_bounded_ok']};"
          f"shed_frac={r['burst_with_admission']['shed_frac']};"
          f"checks_overhead={r['robust_checks_overhead']};"
          f"checks_overhead_ok={r['checks_overhead_ok']};"
          f"reload_retraces={r['reload_retraces']};"
          f"degraded_exact_ok={r['degraded_exact_ok']}")


def _bench_slda_sparse(args):
    from . import bench_slda_sparse
    r = bench_slda_sparse.run(quick=not args.full)["results"]
    speed = ";".join(f"T{t}={s}x"
                     for t, s in r["speedup_by_topics"].items())
    modeled = ";".join(f"T{t}={s}x"
                       for t, s in r["modeled_speedup_by_topics"].items())
    print(f"slda_sparse,0,measured:{speed};modeled:{modeled};"
          f"mse_guard_ok={r['mse_guard_ok']};"
          f"dense_wins_small_t={r['dense_wins_small_t']}")


def _bench_roofline(args):
    try:
        from . import roofline
        rows = roofline.load()
        for d in rows:
            tag = (f"{d['arch']}_{d['shape']}_"
                   f"{'multi' if d['multi_pod'] else 'single'}")
            print(f"roofline_{tag},{d['compile_s'] * 1e6:.0f},"
                  f"dom={d['dominant']};frac={d['roofline_frac']:.3f}")
    except Exception as e:  # noqa: BLE001 — artifacts may not exist yet
        print(f"roofline_skipped,0,{e!r}", file=sys.stderr)


#: name → (runner, run_by_default) — opt-in extras run only via --only
BENCHES = {
    "fig6": (_bench_fig6, True),
    "fig7": (_bench_fig7, True),
    "ablation": (_bench_ablation, False),
    "kernels": (_bench_kernels, True),
    "slda_predict": (_bench_slda_predict, False),
    "slda_train": (_bench_slda_train, False),
    "slda_parallel": (_bench_slda_parallel, False),
    "slda_ragged": (_bench_slda_ragged, False),
    "slda_robust": (_bench_slda_robust, False),
    "slda_elastic": (_bench_slda_elastic, False),
    "slda_serving": (_bench_slda_serving, False),
    "slda_serving_robust": (_bench_slda_serving_robust, False),
    "slda_sparse": (_bench_slda_sparse, False),
    "roofline": (_bench_roofline, True),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale corpora (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma list from the registry: "
                         + ",".join(BENCHES))
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    unknown = (only or set()) - set(BENCHES)
    if unknown:
        ap.error(f"unknown bench(es): {sorted(unknown)}")

    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, (fn, default_on) in BENCHES.items():
        if (only is None and default_on) or (only is not None
                                             and name in only):
            fn(args)


if __name__ == "__main__":
    main()
