"""NGDB-Zoo training driver (the paper's kind: training).

Runs the full loop — online sampling, operator-level scheduling, fused
execution, vectorized loss, Adam — with checkpoint/auto-resume and optional
decoupled semantic augmentation and adaptive sampling.

  PYTHONPATH=src python -m repro.launch.train --dataset FB15k --model betae \
      --steps 200 --batch-size 128 --dim 64 --semantic --ckpt-dir /tmp/ckpt

Semantic at scale (DESIGN.md §SemanticStore): pass ``--semantic-store DIR``
to keep H_sem on disk (sharded mmap, built once, reused across runs) with
only a bounded device-resident hot set:

  PYTHONPATH=src python -m repro.launch.train --dataset FB15k --model gqe \
      --semantic --semantic-store /tmp/sem --semantic-budget-rows 2048 \
      --semantic-quant fp32 --pipeline --steps 200

At the published size (Table 4 graph, dim 400, the NGDB batch):

  PYTHONPATH=src python -m repro.launch.train --dataset FB15k-237 \
      --full-scale --model gqe --dim 400 --batch-size 512 --negatives 64

``main(argv)`` returns the trainer and the eval metrics, so one process can
drive training and check what it produced (``chip_smoke.py`` does).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional, Sequence

import numpy as np

from repro.data import load_dataset
from repro.distributed.context import make_execution_context
from repro.models import ModelConfig, make_model, model_names
from repro.obs import TRACER, get_registry
from repro.sampling import OnlineSampler
from repro.semantic import (PTEConfig, SemanticCache, SemanticStore,
                            SemanticStoreError, StubPTE,
                            precompute_semantic_table,
                            precompute_semantic_table_to_store)
from repro.training import AdamConfig, NGDBTrainer, TrainConfig, evaluate
from repro.xla_cache import enable_persistent_cache


def open_or_build_store(directory: str, kg, d_l: int, quant: str,
                        shard_rows: int = 65536) -> SemanticStore:
    """Reuse a complete store if one is already on disk (matching shape and
    quant layout); otherwise stream the offline precompute into it."""
    try:
        store = SemanticStore(directory)
        if (store.n_rows, store.dim, store.quant) == (kg.n_entities, d_l, quant):
            print(f"semantic store: reusing {directory} "
                  f"({store.n_rows}x{store.dim} {store.quant}, "
                  f"{store.disk_nbytes/1e6:.1f} MB on disk)")
            return store
        print("semantic store: shape/quant mismatch — rebuilding")
    except SemanticStoreError as e:
        print(f"semantic store: {e}")
    t0 = time.time()
    pte = StubPTE(PTEConfig(d_l=d_l, n_layers=2, d_model=128))
    store = precompute_semantic_table_to_store(
        kg, directory, pte, quant=quant, shard_rows=shard_rows)
    print(f"semantic store: built {store.n_rows}x{store.dim} {quant} at "
          f"{directory} in {time.time()-t0:.1f}s "
          f"({store.disk_nbytes/1e6:.1f} MB, PTE unloaded)")
    return store


def main(argv: Optional[Sequence[str]] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="FB15k")
    ap.add_argument("--full-scale", action="store_true",
                    help="build the graph at the exact Table 4 statistics "
                         "instead of the small reduced stand-in")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the synthetic graph, the params and the "
                         "query sampler")
    ap.add_argument("--model", default="betae", choices=model_names())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--negatives", type=int, default=32)
    ap.add_argument("--semantic", action="store_true")
    ap.add_argument("--semantic-dim", type=int, default=256)
    ap.add_argument("--semantic-store", default=None, metavar="DIR",
                    help="out-of-core H_sem: sharded mmap store on disk + a "
                         "bounded device-resident hot-set cache (implies "
                         "--semantic); built at DIR on first use")
    ap.add_argument("--semantic-budget-rows", type=int, default=0,
                    help="device hot-set row budget for --semantic-store "
                         "(0 = auto: 4x the per-batch working set)")
    ap.add_argument("--semantic-quant", default="fp32",
                    choices=["fp32", "int8"],
                    help="on-disk layout: fp32 is bit-identical to "
                         "full-resident training; int8 is 4x smaller with "
                         "per-row scales")
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--executor", default="pooled", choices=["pooled", "query_level"])
    ap.add_argument("--no-cse", action="store_true",
                    help="ablation: disable the plan compiler's cross-query "
                         "subexpression sharing (DESIGN.md §Compiler) — "
                         "every query node becomes its own pooled row, the "
                         "pre-compiler behavior")
    ap.add_argument("--materialized-rows", type=int, default=0,
                    help="attach a MaterializedSubqueryCache of N encoded "
                         "rows to the pooled executor's eval/encode path "
                         "(version-stamped: invalidated on every param "
                         "update and KG write; 0 = off)")
    ap.add_argument("--pipeline", action="store_true",
                    help="pipelined dataflow mode: overlap Algorithm-1 "
                         "scheduling for batch k+1 with device execution of "
                         "batch k (sync mode is the ablation baseline); with "
                         "--semantic-store this also prefetches semantic rows "
                         "on the scheduler thread (zero mid-step store reads)")
    ap.add_argument("--max-inflight", type=int, default=2,
                    help="pipelined dispatch window (2 = double-buffered)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="mesh-shard the run: data=N[,model=M] (DESIGN.md "
                         "§Sharding). Tables/Adam state materialize into "
                         "their NamedShardings and the fused step compiles "
                         "with explicit in/out shardings; omit for the "
                         "single-device default. On a CPU host emulate "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--profile", default="2d", choices=["2d", "fsdp"],
                    help="sharding profile for --mesh: 2d = TP x FSDP rule "
                         "table; fsdp = ZeRO-3 (every large table/param "
                         "shards its largest divisible dim over all devices "
                         "— the profile that splits the entity table 1/N "
                         "on a pure data mesh)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--live-writes", type=int, default=0, metavar="N",
                    help="post-training live-write smoke (DESIGN.md "
                         "§LiveStore): commit N fresh triple bursts into the "
                         "trained KG and incrementally fine-tune the written "
                         "neighborhoods from the trained params")
    ap.add_argument("--eval-queries", type=int, default=64)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace-event/Perfetto JSON timeline "
                         "of the run (thread lanes: main dispatch, pipeline "
                         "scheduler, sampling workers; spans: sample/schedule"
                         "/compile/transfer/sem_prefetch/store_io/dispatch/"
                         "retire). Load at ui.perfetto.dev")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write per-step phase durations + bubble fraction "
                         "as JSONL, with a final registry snapshot record; "
                         "summarize with python -m repro.obs.report")
    ap.add_argument("--autotune-cache", default=None, metavar="PATH",
                    help="persisted kernel-tile autotune cache (DESIGN.md "
                         "§Autotuner): tuned tile configs load from PATH and "
                         "make pool padding kernel-aware; also the default "
                         "via REPRO_AUTOTUNE_CACHE (run.sh sets it)")
    ap.add_argument("--autotune", action="store_true",
                    help="run the bounded tile sweep for this model/shape "
                         "regime before training (results persist to "
                         "--autotune-cache when given); without this flag "
                         "only already-tuned configs are used")
    args = ap.parse_args(argv)
    print(f"persistent compile cache: {enable_persistent_cache()}")
    if args.semantic_store:
        args.semantic = True
    if args.trace:
        TRACER.enable()
        TRACER.set_lane("main dispatch")

    ctx = make_execution_context(args.mesh, profile=args.profile)
    if ctx.is_sharded:
        print(f"execution context: {ctx.describe()} "
              f"({ctx.n_devices} devices, dp={ctx.dp_size})")

    kg, full_kg, stats = load_dataset(args.dataset,
                                      reduced=not args.full_scale,
                                      seed=args.seed)
    scale = "Table 4 scale" if args.full_scale else "reduced stand-in"
    print(f"dataset={args.dataset} ({scale}): "
          f"{kg.n_entities} entities, {kg.n_relations} relations, {len(kg)} train triples")

    table, store, cache = None, None, None
    sem_dim = 0
    if args.semantic_store:
        sem_dim = args.semantic_dim
        store = open_or_build_store(args.semantic_store, kg, sem_dim,
                                    args.semantic_quant)
        # Working set of one step: anchors (<=3/query) + positive + negatives.
        per_batch = args.batch_size * (4 + args.negatives)
        budget = args.semantic_budget_rows or min(kg.n_entities, 4 * per_batch)
        budget = max(budget, min(kg.n_entities, per_batch))
        cache = SemanticCache(store, budget_rows=budget, ctx=ctx)
        print(f"semantic cache: {budget} device rows "
              f"({cache.device_resident_sem_bytes/1e6:.2f} MB device-resident "
              f"vs {kg.n_entities * sem_dim * 4/1e6:.2f} MB full-resident)")
    elif args.semantic:
        t0 = time.time()
        pte = StubPTE(PTEConfig(d_l=args.semantic_dim, n_layers=2, d_model=128))
        table = precompute_semantic_table(kg, pte)
        sem_dim = args.semantic_dim
        print(f"semantic precompute: {table.shape} in {time.time()-t0:.1f}s; PTE unloaded")

    # Pad entity rows to a multiple of the mesh size so the tables divide
    # whichever axis the profile assigns them (§Perf: indivisible rows make
    # the rule table silently replicate the biggest buffer in the run).
    model = make_model(args.model, ModelConfig(dim=args.dim, gamma=12.0,
                                               semantic_dim=sem_dim,
                                               entity_pad=max(1, ctx.n_devices)))
    # Kernel autotuning must be settled BEFORE the trainer exists: the
    # executor snapshots its kernel-aware tile policy at construction.
    if args.autotune_cache or args.autotune:
        from repro.kernels import autotune as kat

        tuner = kat.KernelTuner(path=args.autotune_cache) \
            if args.autotune_cache else kat.get_tuner()
        if args.autotune_cache:
            kat.set_tuner(tuner)
        if args.autotune:
            t0 = time.time()
            n_sw = kat.tune_for_model(model, tuner, batch=args.batch_size)
            print(f"autotune: {n_sw} sweeps in {time.time()-t0:.1f}s, "
                  f"{len(tuner)} cached configs"
                  + (f" @ {tuner.path}" if tuner.path else ""))
        elif len(tuner):
            print(f"autotune: {len(tuner)} tuned configs loaded"
                  + (f" from {tuner.path}" if tuner.path else ""))
    cfg = TrainConfig(
        batch_size=args.batch_size, n_negatives=args.negatives,
        adam=AdamConfig(lr=args.lr), adaptive=args.adaptive,
        executor=args.executor, checkpoint_dir=args.ckpt_dir,
        pipeline=args.pipeline, max_inflight=args.max_inflight,
        cse=not args.no_cse, materialized_rows=args.materialized_rows,
        metrics_path=args.metrics, seed=args.seed,
    )
    trainer = NGDBTrainer(model, kg, cfg, semantic_table=table,
                          semantic_cache=cache, ctx=ctx)
    if trainer.resume():
        print(f"resumed from checkpoint at step {trainer.step}")

    t0 = time.time()
    trainer.train(args.steps, log_every=args.log_every)
    dt = time.time() - t0
    if args.metrics and trainer.metrics_sink.enabled:
        trainer.metrics_sink.write({"kind": "snapshot",
                                    "metrics": get_registry().snapshot()})
        trainer.metrics_sink.close()
    if args.trace:
        TRACER.write(args.trace)
        TRACER.disable()
        print(f"trace: wrote {args.trace} (load at ui.perfetto.dev)")
    qps = args.steps * args.batch_size / dt
    # pipeline mode requires the pooled executor; train() falls back to the
    # sync loop otherwise — report what actually ran.
    mode = "pipelined" if (args.pipeline and args.executor == "pooled") else "sync"
    if args.pipeline and mode == "sync":
        print("note: --pipeline requires --executor pooled; ran the sync path")
    cc = trainer.compile_cache_stats()["train_step"]
    print(f"trained {args.steps} steps [{mode}] in {dt:.1f}s ({qps:.0f} queries/sec)")
    print(f"compile cache: {cc['size']} programs, "
          f"hit rate {cc['hit_rate']:.2%} ({cc['misses']} traces)")
    sh = trainer.executor.sharing_stats()
    # Report the executor's ACTUAL mode: the query-level baseline pins CSE
    # off regardless of the flag (sharing would hand it the pooled win).
    cse_on = getattr(trainer.executor, "cse", False)
    print(f"plan compiler: CSE {'on' if cse_on else 'off'}"
          f"{' (query-level baseline)' if args.executor != 'pooled' else ''}"
          f" — {sh['pooled_rows_saved']} pooled rows saved "
          f"({sh['saved_frac']:.1%} of {sh['nodes_before']})")
    pc = sh.get("plan_cache")
    if pc is not None:
        print(f"plan cache: {pc['size']} canonical plans, "
              f"hit rate {pc['hit_rate']:.2%} "
              f"({pc['canonicalize_calls']} canonicalizations, "
              f"{pc['misses']} rebuilds)")
    mc = sh.get("materialized")
    if mc is not None:
        print(f"materialized rows: hit rate {mc['hit_rate']:.2%}, "
              f"{mc['live']} live rows, {mc['invalidations']} invalidations "
              f"({mc['stale_drops']} stale inserts dropped)")
    if ctx.is_sharded:
        ent = trainer.params["entity"]
        per_dev = ent.addressable_shards[0].data.nbytes
        print(f"entity table: {ent.nbytes/1e6:.2f} MB logical, "
              f"{per_dev/1e6:.2f} MB/device "
              f"({ent.sharding.spec} over {ctx.describe()})")
    if cache is not None:
        cs = cache.stats()
        print(f"semantic cache: hit rate {cs['hit_rate']:.2%}, "
              f"{cs['evictions']} evictions, "
              f"{cs['device_resident_sem_bytes']/1e6:.2f} MB device-resident, "
              f"prefetch overlap {cs['prefetch_overlap_frac']:.2%} "
              f"({cs['sync_stages']} synchronous mid-step reads)")

    if args.live_writes > 0:
        if cache is not None:
            print("live-write smoke skipped: hot-set (sem_cache) params do "
                  "not support live maintenance")
        else:
            from repro.training.loop import incremental_finetune

            wrng = np.random.default_rng(29)
            v0 = kg.graph_version
            for i in range(args.live_writes):
                cand = np.stack([wrng.integers(0, kg.n_entities, 16),
                                 wrng.integers(0, kg.n_relations, 16),
                                 wrng.integers(0, kg.n_entities, 16)], axis=1)
                fresh = kg.insert_triples(cand[~kg.contains(cand)][:4])
                if not len(fresh):
                    continue
                trainer.params, losses = incremental_finetune(
                    model, trainer.params, fresh, lr=args.lr,
                    seed=kg.graph_version, executor=trainer.executor)
                print(f"live write {i}: v{kg.graph_version} "
                      f"{len(fresh)} fresh triples, fine-tune loss "
                      f"{losses[0]:.4f} -> {losses[-1]:.4f}")
            print(f"live-write smoke: graph version {v0} -> "
                  f"{kg.graph_version}, {len(kg)} triples")

    eval_qs = [b.query for b in OnlineSampler(kg, seed=123).sample_batch(args.eval_queries)]
    score_all_fn = None
    if cache is not None:
        # Encoding eval queries gathers their anchors through the cache;
        # stage them once up front. Scoring streams H_sem from the store.
        anchors = np.unique(np.concatenate([q.anchors for q in eval_qs]))
        try:
            stage = cache.plan(anchors)
        except RuntimeError as e:
            print(f"eval skipped: {e}")
            return {"trainer": trainer, "eval": None, "mode": mode}
        if stage is not None:
            trainer.params = cache.apply_to(trainer.params, stage)
        score_all_fn = lambda p, q: model.score_all_chunked(p, q, store.read_rows)  # noqa: E731
    metrics = evaluate(model, trainer.params, trainer.executor, full_kg,
                       eval_qs, train_kg=kg, score_all_fn=score_all_fn)
    print("eval:", json.dumps({k: round(float(v), 4) for k, v in metrics.items()}))
    return {"trainer": trainer, "eval": metrics, "mode": mode}


if __name__ == "__main__":
    main()
