"""Table 3 / Table 1: end-to-end training throughput, operator-level
(NGDB-Zoo) vs query-level (KGReasoning/SQE-style) batching, across backbone
models and datasets. CPU-scale reduction of the paper's protocol; the metric
of record is the RELATIVE speedup and the schedule statistics (pool fill,
slot reuse), which are hardware-independent.

Protocol: steady-state (the paper trains tens of thousands of steps, so
compile cost amortizes to zero). We pre-sample a fixed list of mixed-pattern
batches, warm BOTH engines on the same list until their jit caches are
signature-stable, then time pure training-step execution over the list.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

if __package__ in (None, ""):  # direct `python benchmarks/throughput.py`
    _root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)

import numpy as np

from benchmarks.common import emit
from repro.data import load_dataset
from repro.models import ModelConfig, make_model
from repro.sampling import OnlineSampler
from repro.training import AdamConfig, NGDBTrainer, TrainConfig


def run(models=("betae", "q2b", "gqe"),
        datasets=("FB15k",), steps: int = 5, batch: int = 64,
        dim: int = 32) -> None:
    """Headline trio by default (Table 1); pass all five for the full Table 3."""
    for ds in datasets:
        kg, _, stats = load_dataset(ds)
        for name in models:
            rows = {}
            for ex_kind in ("pooled", "query_level"):
                model = make_model(name, ModelConfig(dim=dim, gamma=6.0))
                cfg = TrainConfig(batch_size=batch, n_negatives=16, b_max=256,
                                  prefetch=0, executor=ex_kind,
                                  adam=AdamConfig(lr=1e-3))
                tr = NGDBTrainer(model, kg, cfg)
                batches = [tr.sampler.sample_batch(batch) for _ in range(steps)]
                for b in batches:  # warm every signature once
                    tr.train_step(b)
                t0 = time.perf_counter()
                for b in batches:  # steady state: all signatures compiled
                    tr.train_step(b)
                dt = time.perf_counter() - t0
                rows[ex_kind] = steps * batch / dt
            speedup = rows["pooled"] / rows["query_level"]
            emit(f"tput/{ds}/{name}/pooled_qps", 1e6 / rows["pooled"],
                 f"qps={rows['pooled']:.0f}")
            emit(f"tput/{ds}/{name}/query_level_qps", 1e6 / rows["query_level"],
                 f"qps={rows['query_level']:.0f}")
            emit(f"tput/{ds}/{name}/speedup", 0.0, f"x{speedup:.2f}")


def _host_parallel_efficiency(seconds: float = 0.8) -> float:
    """How much concurrent progress a Python thread and a GIL-releasing
    compute thread make on this host, summed in units of their solo rates
    (2.0 = two independent cores, 1.0 = a single effective core / no
    overlap possible). The pipelined engine overlaps exactly these two kinds
    of work, so its wall-clock win is physically bounded by this number —
    emitted so the speedup below is interpretable on small/shared machines."""
    import threading

    a = np.random.default_rng(0).normal(size=(384, 384)).astype(np.float32)

    def compute(count, stop):  # numpy matmul releases the GIL
        while not stop[0]:
            (a @ a).sum()
            count[0] += 1

    def python_work(count, stop):  # interpreter-bound, holds the GIL
        x = 0
        while not stop[0]:
            x = (x + 1) % 1000003
            count[0] += 1

    def run(workers) -> List[float]:
        counts = [[0] for _ in workers]
        stop = [False]
        ts = [threading.Thread(target=w, args=(c, stop))
              for w, c in zip(workers, counts)]
        for t in ts:
            t.start()
        time.sleep(seconds)
        stop[0] = True
        for t in ts:
            t.join()
        return [c[0] / seconds for c in counts]

    comp_solo = run([compute])[0]
    py_solo = run([python_work])[0]
    comp_c, py_c = run([compute, python_work])
    return comp_c / max(comp_solo, 1) + py_c / max(py_solo, 1)


def run_pipeline_compare(steps: int = 20, batch: int = 1024, dim: int = 64,
                         model_name: str = "gqe", negatives: int = 32,
                         dataset: str = "FB15k", trials: int = 3) -> float:
    """Sync vs pipelined dataflow execution on an identical end-to-end
    synthetic workload — online sampling → training arrays → Algorithm-1
    scheduling → fused device step (DESIGN.md §Pipeline).

    The batch stream is a seeded sampler: every pass (and both engines) sees
    the exact same batch sequence, so the signature set is fixed and the
    compile cache must report ZERO retraces across all timed passes. Sync
    runs all stages strictly in sequence on one thread (the ablation
    baseline); pipelined overlaps the host stages with device execution.
    Timed passes are interleaved (S,P,S,P,...) so machine-speed drift hits
    both engines equally, and min-time per mode rejects co-tenant noise
    spikes. Steady-state claims: ZERO retraces (asserted — 100% compile
    cache hit rate), and pipelined >= 1.3x sync steps/sec wherever the host
    can actually overlap (reported; physically bounded by the emitted
    host_parallel_efficiency — see DESIGN.md §Pipeline)."""
    eff = _host_parallel_efficiency()
    emit(f"pipeline/{dataset}/{model_name}/host_parallel_efficiency", 0.0,
         f"{eff:.2f} (2.0=two independent cores, 1.0=no overlap possible)")

    kg, _, _ = load_dataset(dataset)
    src = OnlineSampler(kg, seed=7)
    replay = [src.sample_batch(batch) for _ in range(steps)]

    def stream():
        """Deterministic batch source: same sequence every pass."""
        it = iter(replay * 1000)
        return lambda: next(it)

    trainers = {}
    for mode in ("sync", "pipelined"):
        model = make_model(model_name, ModelConfig(dim=dim, gamma=6.0))
        cfg = TrainConfig(batch_size=batch, n_negatives=negatives, b_max=256,
                          prefetch=2, executor="pooled",
                          pipeline=(mode == "pipelined"),
                          adam=AdamConfig(lr=1e-3), seed=0)
        tr = NGDBTrainer(model, kg, cfg)
        tr.train(steps, log_every=0, batches=stream())  # warm every signature
        tr._train_fns.reset_counters()
        trainers[mode] = tr

    best = {"sync": float("inf"), "pipelined": float("inf")}
    for _ in range(max(trials, 1)):
        for mode, tr in trainers.items():
            t0 = time.perf_counter()
            tr.train(steps, log_every=0, batches=stream())  # steady-state
            best[mode] = min(best[mode], time.perf_counter() - t0)

    qps = {}
    for mode, tr in trainers.items():
        qps[mode] = steps * batch / best[mode]
        cc = tr._train_fns.stats()
        emit(f"pipeline/{dataset}/{model_name}/{mode}_steps_per_sec",
             1e6 * best[mode] / steps,
             f"steps/s={steps / best[mode]:.2f} qps={qps[mode]:.0f}")
        emit(f"pipeline/{dataset}/{model_name}/{mode}_cache_hit_rate", 0.0,
             f"{cc['hit_rate']:.2%} ({cc['misses']} retraces)")
        assert cc["misses"] == 0, (
            f"{mode}: {cc['misses']} retraces after warmup — the bucketed "
            f"signature set must be compile-stable on a replayed workload")
    speedup = qps["pipelined"] / qps["sync"]
    emit(f"pipeline/{dataset}/{model_name}/speedup", 0.0, f"x{speedup:.2f}")
    return speedup


def run_schedule_stats(batch: int = 512) -> None:
    """Memory-side claim (Eq. 7): slot reuse vs query-scoped allocation, and
    the kernel-count claim (Eq. 4/5): pooled steps vs fragmented launches."""
    from repro.core import PooledExecutor, build_batched_dag, schedule
    from repro.sampling import OnlineSampler

    kg, _, _ = load_dataset("FB15k")
    sampler = OnlineSampler(kg, seed=0)
    queries = [b.query for b in sampler.sample_batch(batch)]
    model = make_model("betae", ModelConfig(dim=16))
    ex = PooledExecutor(model, b_max=512)
    prepared = ex.prepare(queries)
    st = prepared.sched.stats
    emit("sched/steps", 0.0, f"{st['steps']}")
    emit("sched/mean_pool_fill", 0.0, f"{st['mean_pool_fill']:.1f}")
    emit("sched/slot_reuse_ratio", 0.0, f"x{st['slot_reuse_ratio']:.2f}")
    emit("sched/pad_waste", 0.0, f"{st['pad_waste']:.3f}")
    # fragmentation comparison: pooled kernel count vs per-pattern grouping
    frag_steps = 0
    groups = {}
    for q in queries:
        groups.setdefault(q.pattern, []).append(q)
    for pat, qs in groups.items():
        frag_steps += len(schedule(build_batched_dag(qs), b_max=512).steps)
    emit("sched/pooled_kernels", 0.0, f"{st['steps']}")
    emit("sched/query_level_kernels", 0.0, f"{frag_steps}")
    emit("sched/kernel_reduction", 0.0, f"x{frag_steps / max(st['steps'],1):.1f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--compare", action="store_true",
                    help="sync vs pipelined dataflow executor + cache hit rate")
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--negatives", type=int, default=32)
    ap.add_argument("--model", default="gqe")
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()
    if args.compare:
        run_pipeline_compare(steps=args.steps, batch=args.batch, dim=args.dim,
                             model_name=args.model, negatives=args.negatives,
                             trials=args.trials)
    else:
        run()
        run_schedule_stats()
