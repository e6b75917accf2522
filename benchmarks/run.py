# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: tput,ops,sem,semstore,"
                         "adaptive,freebase,scaling,kernels,pipeline,serving,"
                         "plan,obs,autotune,live")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    from benchmarks import (adaptive, autotune, kernels_bench, live, obs,
                            operator_speedup, plan, runtime_freebase,
                            scaling, semantic, serving, throughput)

    suites = [
        ("tput", "Table 3/1: operator-level vs query-level throughput",
         lambda: (throughput.run(), throughput.run_schedule_stats())),
        ("ops", "Table 6: per-operator batched speedup", operator_speedup.run),
        ("sem", "Table 8/Fig 8: decoupled semantic integration", semantic.run),
        ("semstore", "§4.4 out-of-core semantic store + hot-set cache",
         semantic.run_store),
        ("adaptive", "Fig 9: adaptive sampling under shift", adaptive.run),
        ("freebase", "Table 2: single-hop completion runtime", runtime_freebase.run),
        # The scaling sweep also persists its summary (per-device param
        # bytes, steps/s, retrace counts) to BENCH_scaling.json at the repo
        # root, so the perf trajectory accumulates across PRs.
        ("scaling", "Fig 7/Table 2: sharded-vs-single-device scaling sweep",
         scaling.run),
        # Persists oracle-agreement + resolved-tile summary to
        # BENCH_kernels.json at the repo root (committed across PRs).
        ("kernels", "Pallas kernel validation/micro (BENCH_kernels.json)",
         kernels_bench.run),
        ("pipeline", "Pipelined dataflow executor vs sync + compile cache",
         throughput.run_pipeline_compare),
        # Also persists its QPS/latency/invariant summary to
        # BENCH_serving.json at the repo root (committed across PRs).
        ("serving", "§Serving: continuous-batching engine load test "
                    "(bit-identity + zero steady-state retraces)",
         serving.run),
        # Persists its sharing/bit-identity/retrace summary to
        # BENCH_plan.json at the repo root (committed across PRs).
        ("plan", "§Compiler: plan-IR CSE on an overlap-heavy replay "
                 "(>=25% pooled rows saved, bitwise losses, zero retraces)",
         plan.run),
        # Persists its overhead/bit-identity/trace-completeness summary to
        # BENCH_obs.json at the repo root (committed across PRs).
        ("obs", "§Observability: tracing overhead gate (off = bit-identical "
                "+ free; on <= 2% pipelined throughput; traces validate)",
         obs.run),
        # Persists its bit-identity/retrace/paired-ratio/cache-roundtrip
        # summary to BENCH_autotune.json at the repo root (committed).
        ("autotune", "§Autotuner: tile sweep gate (tuned bitwise vs default, "
                     "zero retraces w/ kernel-aware bucketing, tuned never "
                     "slower, persisted cache serves run 2)",
         autotune.run),
        # Persists its continuity/pinned-replay/staleness/determinism
        # summary to BENCH_live.json at the repo root (committed across PRs).
        ("live", "§LiveStore: live KG writes under serving load (zero "
                 "failed requests, pinned replay bitwise vs snapshot "
                 "oracle, typed staleness sheds, deterministic background "
                 "fine-tune)",
         live.run),
    ]
    print("name,us_per_call,derived")
    failed = []
    for key, desc, fn in suites:
        if want and key not in want:
            continue
        print(f"# {desc}", flush=True)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            print(f"{key}/ERROR,0.0,failed")
            failed.append(key)
    if failed:
        sys.exit(f"failed suites: {','.join(failed)}")


if __name__ == "__main__":
    main()
