"""NGDB serving driver — a thin CLI over the continuous-batching engine
(``repro/serving``, DESIGN.md §Serving).

Generates a deterministic mixed-pattern request stream and drives the
``ServingEngine`` either closed-loop (``--concurrency`` requests in flight —
the max-throughput probe) or open-loop (``--qps`` fixed arrival rate — the
latency-under-load probe), reporting QPS, p50/p95/p99 latency, flush/batch
shape statistics and steady-state retrace counts.

Composes with the rest of the launch surface:

* ``--semantic-store DIR`` serves out-of-core (DESIGN.md §SemanticStore):
  anchors stage into the bounded device hot set on the batcher thread, and
  all-entity scoring streams H_sem from the mmap store in chunks.
* ``--mesh data=N[,model=M]`` serves mesh-sharded (DESIGN.md §Sharding):
  tables materialize into their NamedShardings and the scorer jit pins its
  logits replicated for host readback.

``serve_batch`` remains the one-shot OFFLINE baseline (used by tests as
the bit-identity oracle): it shares the engine's compiled
encode programs and process-wide cached scorer, so the two paths produce
identical results on identical micro-batch compositions — and repeated
calls trace ``score_all`` exactly once (the historical per-call re-jit is
fixed by routing through ``serving.scorer_for``).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from repro.core import PooledExecutor
from repro.data import load_dataset
from repro.distributed.context import make_execution_context
from repro.models import ModelConfig, make_model, model_names
from repro.obs import MetricsSink, TRACER, get_registry
from repro.serving import (ServingConfig, ServingEngine, make_workload,
                           run_closed_loop, run_open_loop, scorer_for,
                           topk_desc)
from repro.training.checkpoint import load_checkpoint
from repro.xla_cache import enable_persistent_cache

__all__ = ["serve_batch", "topk_desc", "main"]  # topk_desc re-exported


def serve_batch(model, params, executor, queries, top_k: int = 10,
                score_all_fn=None, sem_cache=None, ctx=None):
    """One-shot synchronous batch serving — the offline baseline the engine
    is verified against. Encoding goes through the executor's per-signature
    compiled programs and scoring through the process-wide cached jit
    (``scorer_for``; pass the engine's ``ctx`` under a mesh so both paths
    resolve the SAME scorer program) — zero retraces across repeated
    calls."""
    if sem_cache is not None:
        if score_all_fn is None:
            # Hot-set-cache params cannot dense-score (score_all refuses the
            # bounded buffer); fail before doing any staging work.
            raise ValueError(
                "serve_batch with sem_cache needs score_all_fn (e.g. "
                "lambda p, q: model.score_all_chunked(p, q, store.read_rows))")
        # Serving counts as synchronous staging (no pipeline in front of it);
        # steady traffic converges to hits as the hot set fills.
        anchors = np.concatenate([q.anchors for q in queries])
        stage = sem_cache.plan(anchors)
        if stage is not None:
            params = sem_cache.apply_to(params, stage)
    states = executor.encode(params, queries, compiled=True)
    if score_all_fn is None:
        score_all_fn = scorer_for(model, ctx)
    scores = np.asarray(score_all_fn(params, states))
    idx = topk_desc(scores, top_k)
    return [
        {"pattern": q.pattern,
         "anchors": q.anchors.tolist(),
         "relations": q.relations.tolist(),
         "top_entities": idx[i].tolist(),
         "scores": scores[i, idx[i]].round(3).tolist()}
        for i, q in enumerate(queries)
    ], params


def _parse_tenants(tenants_spec, mix_spec):
    """``--tenants "gold:high,bronze:low[:quota]"`` and
    ``--priority-mix "gold=0.25,bronze=0.75"`` -> (specs, weights).
    With no ``--tenants``, everything rides the router's default tenant."""
    from repro.serving import TenantSpec

    if not tenants_spec:
        return [], {}
    specs = []
    for part in tenants_spec.split(","):
        bits = part.strip().split(":")
        if len(bits) not in (2, 3):
            raise ValueError(f"tenant spec {part!r}: want name:priority"
                             f"[:max_inflight]")
        quota = int(bits[2]) if len(bits) == 3 else 0
        specs.append(TenantSpec(bits[0], bits[1], quota))
    weights = {s.name: 1.0 for s in specs}
    if mix_spec:
        weights = {}
        for part in mix_spec.split(","):
            name, w = part.split("=")
            weights[name.strip()] = float(w)
        unknown = set(weights) - {s.name for s in specs}
        if unknown:
            raise ValueError(f"--priority-mix names unknown tenants "
                             f"{sorted(unknown)}")
    total = sum(weights.values())
    return specs, {n: w / total for n, w in weights.items()}


def _serve_tier(args, kg, model, params, ctx) -> None:
    """Multi-replica serving tier (DESIGN.md §ServingTier): rendezvous
    plan-cache-affinity routing over ``--replicas`` engines with per-tenant
    priority admission and typed low-priority sheds."""
    from repro.serving import (ReplicaPool, Router, TenantLoad, run_tenant_mix)

    specs, weights = _parse_tenants(args.tenants, args.priority_mix)
    cfg = ServingConfig(max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms,
                        queue_depth=args.queue_depth, top_k=args.top_k)
    pool = ReplicaPool(model, params, n_replicas=args.replicas, cfg=cfg,
                       mat_budget_rows=args.materialize, ctx=ctx)
    router = Router(pool, tenants=specs)
    workload = make_workload(kg, args.requests, seed=7)

    # Warmup compiles every signature each home replica will see (placement
    # is deterministic, so the timed pass replays onto warm caches).
    t0 = time.time()
    for f in router.submit_many(workload):
        f.result(timeout=120.0)
    print(f"warmup: {args.requests} requests over {args.replicas} replicas "
          f"in {time.time()-t0:.1f}s")
    pool.reset_counters()

    if specs:
        loads = []
        start = 0
        for s in specs:  # contiguous weighted shares, submission-paced
            n = max(1, int(round(weights[s.name] * len(workload))))
            qs = (workload[start:start + n]
                  or workload[: max(1, len(workload) // len(specs))])
            start += len(qs)
            loads.append(TenantLoad(s.name, qs,
                                    qps=args.qps * weights[s.name]))
        reports = run_tenant_mix(router, loads)
        for name in sorted(reports):
            print(reports[name].describe())
    else:
        report = run_open_loop(engine=router, queries=workload, qps=args.qps)
        print(report.describe())

    st = router.stats()
    for rid, rs in sorted(st["pool"]["per_replica"].items()):
        mc = rs.get("mat_cache")
        mat = (f", mat hit rate {mc['hit_rate']:.2%}" if mc else "")
        print(f"replica {rid}: {rs['submitted']} requests, "
              f"{rs['batches']} micro-batches, "
              f"{rs['retraces']} steady-state retraces{mat}")
    print(f"router: {st['routed']} routed, {st['spilled']} spilled, "
          f"{st['shed']} shed")
    for name, ts in sorted(st["tenants"].items()):
        if ts["submitted"] or ts["shed"]:
            sheds = {r: c for r, c in ts["shed"].items() if c}
            print(f"tenant {name} ({ts['priority']}): "
                  f"{ts['completed']}/{ts['submitted']} completed, "
                  f"shed {sheds or 0}, p99 {ts['latency_ms']['p99']:.1f} ms")
    if args.metrics:
        with MetricsSink(args.metrics) as sink:
            sink.write({"kind": "snapshot",
                        "metrics": get_registry().snapshot()})
        print(f"metrics: wrote {args.metrics}")
    router.close()


def main(argv: Optional[Sequence[str]] = None) -> Optional[Dict]:
    """Serve a generated workload; returns the load report, the workload
    and the served params (None for the multi-replica tier)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="FB15k")
    ap.add_argument("--full-scale", action="store_true",
                    help="build the graph at the exact Table 4 statistics "
                         "instead of the small reduced stand-in")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the synthetic graph (match the training "
                         "run's --seed)")
    ap.add_argument("--model", default="betae", choices=model_names())
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=256,
                    help="total requests in the generated workload")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop arrival rate; 0 = closed loop at "
                         "--concurrency in-flight requests")
    ap.add_argument("--concurrency", type=int, default=32,
                    help="closed-loop in-flight window (ignored with --qps)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="engine micro-batch size-flush threshold")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="engine age-flush: max wait of the oldest pending "
                         "request before a partial batch dispatches")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="bounded admission queue (backpressure limit)")
    ap.add_argument("--materialize", type=int, default=0, metavar="N",
                    help="materialized-subquery cache: keep up to N encoded "
                         "rows keyed by query, consulted by the batcher "
                         "before padding so duplicate-heavy traffic skips "
                         "re-encoding entirely (version-stamped — "
                         "invalidated on param updates and KG writes; "
                         "0 = off)")
    ap.add_argument("--no-cse", action="store_true",
                    help="ablation: disable cross-query subexpression "
                         "sharing in the plan compiler (duplicate subqueries "
                         "across co-batched requests are recomputed per "
                         "request)")
    ap.add_argument("--semantic-store", default=None, metavar="DIR",
                    help="serve out-of-core: H_sem stays on disk; device "
                         "holds only the hot-set cache (built by "
                         "launch/train.py --semantic-store)")
    ap.add_argument("--semantic-budget-rows", type=int, default=2048)
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="mesh-shard serving: data=N[,model=M] (DESIGN.md "
                         "§Sharding); emulate devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--profile", default="2d", choices=["2d", "fsdp"])
    ap.add_argument("--latency-window", type=int, default=None,
                    help="latency percentile window size (requests); "
                         "default = engine's built-in window")
    ap.add_argument("--client-threads", type=int, default=1,
                    help="closed-loop client submitter threads (each is a "
                         "named lane in the trace; ignored with --qps)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace-event/Perfetto JSON timeline "
                         "of the timed replay (lanes: client N, serving "
                         "batcher; spans: request/batch/sem_prefetch/encode/"
                         "score/select). Load at ui.perfetto.dev")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write a final registry snapshot (engine counters, "
                         "latency histogram, cache stats) as JSONL; "
                         "summarize with python -m repro.obs.report")
    ap.add_argument("--max-staleness", type=int, default=0, metavar="V",
                    help="staleness-bounded serving (DESIGN.md §LiveStore): "
                         "attach the live graph and admit version-pinned "
                         "requests up to V graph versions behind; out-of-"
                         "bound pins are shed with StaleVersionError")
    ap.add_argument("--live-writes", type=int, default=0, metavar="N",
                    help="fire N live write bursts through LiveNGDB during "
                         "the timed replay (graph commit + background "
                         "incremental fine-tune) and report graph version / "
                         "stale sheds / fine-tune count")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="multi-replica serving tier (DESIGN.md "
                         "§ServingTier): N engines with private plan/"
                         "materialized caches behind a rendezvous-affinity "
                         "router; 1 (default) = the single-engine path, "
                         "byte-for-byte unchanged")
    ap.add_argument("--tenants", default=None, metavar="SPEC",
                    help="router tenants as name:priority[:max_inflight],"
                         "... e.g. 'gold:high,bronze:low' — low priority is "
                         "shed (typed, never blocking) under backpressure; "
                         "needs --replicas")
    ap.add_argument("--priority-mix", default=None, metavar="SPEC",
                    help="traffic share per tenant, e.g. "
                         "'gold=0.25,bronze=0.75' (default: equal shares); "
                         "needs --tenants")
    ap.add_argument("--autotune-cache", default=None, metavar="PATH",
                    help="persisted kernel-tile autotune cache (DESIGN.md "
                         "§Autotuner): tuned configs load from PATH and the "
                         "serving executor pads pools kernel-aware; also the "
                         "default via REPRO_AUTOTUNE_CACHE (run.sh sets it)")
    args = ap.parse_args(argv)
    print(f"persistent compile cache: {enable_persistent_cache()}")

    ctx = make_execution_context(args.mesh, profile=args.profile)
    if ctx.is_sharded:
        print(f"execution context: {ctx.describe()} "
              f"({ctx.n_devices} devices, dp={ctx.dp_size})")

    kg, _, _ = load_dataset(args.dataset, reduced=not args.full_scale,
                            seed=args.seed)
    store, cache = None, None
    sem_dim = 0
    if args.semantic_store:
        from repro.semantic import SemanticCache, SemanticStore

        store = SemanticStore(args.semantic_store)
        assert store.n_rows == kg.n_entities, (store.n_rows, kg.n_entities)
        sem_dim = store.dim
        cache = SemanticCache(store, budget_rows=min(args.semantic_budget_rows,
                                                     kg.n_entities), ctx=ctx)
        print(f"semantic store: {store.n_rows}x{store.dim} {store.quant}, "
              f"{cache.device_resident_sem_bytes/1e6:.2f} MB device-resident")
    model = make_model(args.model,
                       ModelConfig(dim=args.dim, semantic_dim=sem_dim,
                                   entity_pad=max(1, ctx.n_devices)))
    params = model.init_params(jax.random.PRNGKey(0), kg.n_entities,
                               kg.n_relations, semantic_cache=cache, ctx=ctx)
    restored_step = None
    if args.ckpt_dir:
        restored = load_checkpoint(args.ckpt_dir,
                                   template={"params": params, "opt": None})
        if restored:
            restored_step, params = restored[0], restored[1]["params"]
            print(f"loaded checkpoint step={restored_step}")
            if cache is not None:
                cache.reset()  # restored cache buffers: nothing resident yet

    if args.autotune_cache:
        # Must land before the executor exists: it snapshots its kernel-aware
        # tile policy from the process tuner at construction.
        from repro.kernels import autotune as kat

        tuner = kat.KernelTuner(path=args.autotune_cache)
        kat.set_tuner(tuner)
        if len(tuner):
            print(f"autotune: {len(tuner)} tuned configs loaded "
                  f"from {tuner.path}")

    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.priority_mix and not args.tenants:
        ap.error("--priority-mix needs --tenants")
    if args.replicas > 1 or args.tenants:
        # The tier composes with dense in-memory serving only: the semantic
        # hot set is one shared device buffer and live-graph versioning is a
        # single-engine axis (see serving/replica.py).
        if args.semantic_store or args.live_writes or args.max_staleness:
            ap.error("--replicas/--tenants do not compose with "
                     "--semantic-store/--live-writes/--max-staleness "
                     "(single-engine features)")
        if args.no_cse:
            ap.error("--no-cse is a single-engine ablation")
        _serve_tier(args, kg, model, params, ctx)
        return

    executor = PooledExecutor(model, b_max=256, ctx=ctx, cse=not args.no_cse)
    mat_cache = None
    if args.materialize > 0:
        from repro.core import MaterializedSubqueryCache

        mat_cache = MaterializedSubqueryCache(args.materialize)
        mat_cache.watch_kg(kg)
        print(f"materialized cache: {args.materialize} rows "
              f"(invalidated on param update / KG write)")
    live = args.live_writes > 0 or args.max_staleness > 0
    if live and cache is not None:
        ap.error("--live-writes/--max-staleness do not compose with "
                 "--semantic-store (the device hot set is incompatible with "
                 "version-pinned replay)")
    cfg = ServingConfig(max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms,
                        queue_depth=args.queue_depth, top_k=args.top_k,
                        max_staleness_versions=args.max_staleness)
    engine = ServingEngine(model, params, executor=executor, cfg=cfg,
                           sem_cache=cache,
                           sem_rows_fn=store.read_rows if store else None,
                           ctx=ctx, mat_cache=mat_cache,
                           latency_window=args.latency_window,
                           kg=kg if live else None)
    workload = make_workload(kg, args.requests, seed=7)

    # Warmup pass compiles every signature the replay will form; the timed
    # pass then reports steady-state numbers (and its retrace count).
    t0 = time.time()
    run_closed_loop(engine, workload, concurrency=args.max_batch)
    print(f"warmup: {args.requests} requests in {time.time()-t0:.1f}s "
          f"({engine.retraces()} cold cache misses)")
    engine.reset_counters()

    # Trace only the timed steady-state replay (the batcher lane registered
    # itself at engine start; lane names survive enable()).
    if args.trace:
        TRACER.enable()
        TRACER.set_lane("loadgen main")
    writer, live_db = None, None
    if args.live_writes > 0:
        import threading

        from repro.serving import LiveNGDB

        live_db = LiveNGDB(model, kg, engine, finetune_steps=2)
        wrng = np.random.default_rng(23)

        def _write_bursts():
            for _ in range(args.live_writes):
                cand = np.stack([wrng.integers(0, kg.n_entities, 16),
                                 wrng.integers(0, kg.n_relations, 16),
                                 wrng.integers(0, kg.n_entities, 16)], axis=1)
                live_db.write(cand[~kg.contains(cand)][:4])
                time.sleep(0.01)

        writer = threading.Thread(target=_write_bursts, name="live-writer")
        writer.start()
    if args.qps > 0:
        report = run_open_loop(engine, workload, qps=args.qps)
    else:
        report = run_closed_loop(engine, workload,
                                 concurrency=args.concurrency,
                                 threads=args.client_threads)
    if writer is not None:
        writer.join()
        live_db.flush()
    if args.trace:
        TRACER.write(args.trace)
        TRACER.disable()
        print(f"trace: wrote {args.trace} (load at ui.perfetto.dev)")
    st = engine.stats()
    print(report.describe())
    print(f"engine: {st['batches']} micro-batches "
          f"(mean size {st['mean_batch_size']:.1f}, flushes {st['flushes']}, "
          f"padded rows {st['padded_row_frac']:.1%}), "
          f"{st['retraces']} steady-state retraces")
    sh = st["sharing"]
    print(f"plan compiler: CSE {'off' if args.no_cse else 'on'} — "
          f"{sh['pooled_rows_saved']} pooled rows saved "
          f"({sh['saved_frac']:.1%}), "
          f"{st['coalesced']} duplicate requests coalesced")
    pc = st.get("plan_cache")
    if pc is not None:
        print(f"plan cache: {pc['size']} canonical plans, "
              f"hit rate {pc['hit_rate']:.2%} "
              f"({pc['canonicalize_calls']} canonicalizations)")
    mc = st.get("mat_cache")
    if mc is not None:
        print(f"materialized rows: hit rate {mc['hit_rate']:.2%} "
              f"({mc['hits']} hits / {mc['misses']} misses), "
              f"{mc['live']} live, {mc['evictions']} evictions")
    if live:
        lag = st.get("version_lag_served", {})
        print(f"live graph: version {st['graph_version']} "
              f"(retained {st['retained_versions']}), "
              f"{st['stale_sheds']} stale sheds, "
              f"lag histogram {dict(sorted(lag.items()))}")
    if live_db is not None:
        n_fresh = sum(r.n_written for r in live_db.receipts)
        print(f"live writes: {len(live_db.receipts)} bursts, "
              f"{n_fresh} fresh triples, "
              f"{live_db.finetunes_done} background fine-tunes")
        live_db.close()
    print(f"first: {json.dumps(report.results[0])[:140]}...")
    if cache is not None:
        cs = cache.stats()
        print(f"semantic cache: hit rate {cs['hit_rate']:.2%}, "
              f"{cs['rows_staged']} rows staged from store")
    if args.metrics:
        with MetricsSink(args.metrics) as sink:
            sink.write({"kind": "snapshot",
                        "metrics": get_registry().snapshot()})
        print(f"metrics: wrote {args.metrics}")
    engine.close()
    return {"report": report, "workload": workload, "model": model,
            "params": params, "restored_step": restored_step, "stats": st}


if __name__ == "__main__":
    main()
