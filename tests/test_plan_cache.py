"""Cross-batch plan reuse + materialized-subquery staleness suite.

Pins the two caches that make CSE strictly dominate (DESIGN.md §Compiler):

* ``PlanCache`` — compiled plans persist across ``prepare()`` calls. An
  exact-key replay is ONE dict lookup: no canonicalization, no hash-consing,
  no schedule lookup. A permuted batch hits the canonical level and only
  rebinds the order permutation.
* ``MaterializedSubqueryCache`` — encoded rows persist across batches,
  version-stamped so no interleaving of {param update, KG write, eviction
  pressure, version pinning} can ever serve a stale row: cached-path encode
  output is asserted BITWISE against a fresh no-cache executor for every
  model family in the zoo.
"""
import jax
import numpy as np
import pytest

from repro.core import (MaterializedSubqueryCache, PooledExecutor)
from repro.data.kg import generate_synthetic_kg
from repro.models import ModelConfig, make_model, model_names
from repro.sampling import OnlineSampler
from repro.serving import (ServingConfig, ServingEngine, make_workload,
                           run_closed_loop)
from repro.training import NGDBTrainer, TrainConfig


def _model_params(kg, name="gqe", dim=8, seed=0):
    model = make_model(name, ModelConfig(dim=dim, gamma=6.0))
    params = model.init_params(jax.random.PRNGKey(seed), kg.n_entities,
                               kg.n_relations)
    return model, params


def _retraces(tr):
    cs = tr.compile_cache_stats()
    return (int(cs["train_step"]["misses"])
            + sum(int(cs[k]["misses"])
                  for k in ("schedule", "encode", "encode_jit")))


# ------------------------------------------------------------ plan cache unit
def test_exact_hit_skips_canonicalization(tiny_kg, mixed_queries):
    """A repeated batch is served from the exact level without touching the
    canonicalize/sort path; a PERMUTED batch hits the canonical level (one
    extra canonicalize, zero rebuilds) and still restores caller order."""
    model, params = _model_params(tiny_kg)
    ex = PooledExecutor(model, b_max=64)
    qs = [b.query for b in mixed_queries][:12]
    plan1 = ex.prepare(qs)
    pc = ex.sharing_stats()["plan_cache"]
    assert (pc["misses"], pc["canonicalize_calls"]) == (1, 1)
    plan2 = ex.prepare(qs)                      # exact replay
    pc = ex.sharing_stats()["plan_cache"]
    assert (pc["hits"], pc["misses"], pc["canonicalize_calls"]) == (1, 1, 1)
    assert plan2 is plan1                       # the cached object itself
    plan3 = ex.prepare(list(reversed(qs)))      # permuted: canonical hit
    pc = ex.sharing_stats()["plan_cache"]
    assert pc["misses"] == 1                    # no rebuild
    assert pc["canonicalize_calls"] == 2
    assert plan3.signature == plan1.signature
    # order restoration through the canonical-hit path is bitwise
    a = np.asarray(ex.encode(params, qs))
    b = np.asarray(ex.encode(params, list(reversed(qs))))
    np.testing.assert_array_equal(b, a[::-1])


def test_cross_batch_replay_hit_rate(tiny_kg):
    """Replaying a multi-batch workload: pass 2 is 100% exact hits with the
    canonicalize count frozen — the compiler is off the steady-state path."""
    model, _ = _model_params(tiny_kg)
    ex = PooledExecutor(model, b_max=64)
    sampler = OnlineSampler(tiny_kg, seed=2)
    batches = [[s.query for s in sampler.sample_batch(16)] for _ in range(6)]
    for b in batches:
        ex.prepare(b)
    ex.reset_cache_counters()
    for b in batches:
        ex.prepare(b)
    pc = ex.sharing_stats()["plan_cache"]
    assert pc["misses"] == 0
    assert pc["hit_rate"] >= 0.9
    assert pc["canonicalize_calls"] == 0


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("cse", [True, False])
def test_trainer_zero_steady_state_retraces(tiny_kg, cse, pipeline):
    """Warm a trainer on a fixed batch list, reset counters, replay: zero
    retraces (train_step/schedule/encode caches all hit) AND the plan cache
    serves every prepare without canonicalizing, in all four
    {sync, pipelined} x {cse, no-cse} configurations."""
    cfg = TrainConfig(batch_size=16, n_negatives=4, b_max=64, seed=0,
                      cse=cse, pipeline=pipeline, prefetch=1)
    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    tr = NGDBTrainer(model, tiny_kg, cfg)
    batches = [tr.sampler.sample_batch(16) for _ in range(3)]
    tr.train(3, log_every=0, batches=batches)   # warm every signature
    tr._train_fns.reset_counters()
    tr.executor.reset_cache_counters()
    tr.train(3, log_every=0, batches=batches)
    assert _retraces(tr) == 0, tr.compile_cache_stats()
    pc = tr.executor.sharing_stats()["plan_cache"]
    assert pc["hit_rate"] >= 0.9
    assert pc["canonicalize_calls"] == 0


def test_engine_replay_reuses_plans_across_engine_instances(tiny_kg):
    """Two serving engines sharing ONE executor: the second engine's replay
    of the same workload runs at zero retraces and 100% plan-cache hits —
    the cache outlives the engine, not just the batch."""
    model, params = _model_params(tiny_kg)
    ex = PooledExecutor(model, b_max=64)
    workload = make_workload(tiny_kg, 24, seed=5)

    def replay():
        # started=False + pre-queued submits: the batcher drains greedily
        # into deterministic max_batch chunks, so both passes execute the
        # SAME micro-batch compositions.
        eng = ServingEngine(model, params, executor=ex, started=False,
                            cfg=ServingConfig(max_batch=8, max_wait_ms=1e3))
        futs = eng.submit_many(workload)
        eng.start()
        res = [f.result(timeout=120) for f in futs]
        eng.close()
        return eng, res

    _, r1 = replay()
    ex.reset_cache_counters()
    eng2, r2 = replay()
    assert eng2.retraces() == 0, eng2.stats()["caches"]
    pc = ex.sharing_stats()["plan_cache"]
    assert pc["misses"] == 0 and pc["canonicalize_calls"] == 0
    assert pc["hit_rate"] == 1.0
    strip = lambda rs: [{k: v for k, v in r.items()  # noqa: E731
                         if k not in ("latency_ms", "batch_size")}
                        for r in rs]
    assert strip(r1) == strip(r2)


def test_engine_duplicate_replay_serves_rows_from_materialized_cache(tiny_kg):
    """Duplicate-heavy traffic through an engine with a materialized-row
    cache: once warm, at least 90% of rows come from the cache and nothing
    compiles."""
    model, params = _model_params(tiny_kg)
    mat = MaterializedSubqueryCache(128)
    mat.watch_kg(tiny_kg)
    uniq = make_workload(tiny_kg, 32, seed=7)
    workload = [uniq[i % len(uniq)] for i in range(4 * len(uniq))]
    with ServingEngine(model, params, executor=PooledExecutor(model, b_max=64),
                       cfg=ServingConfig(max_batch=16),
                       mat_cache=mat) as engine:
        run_closed_loop(engine, workload, concurrency=16)
        engine.reset_counters()
        run_closed_loop(engine, workload, concurrency=16)
        st = engine.stats()
    assert st["mat_cache"]["hit_rate"] >= 0.9, st["mat_cache"]
    assert st["retraces"] == 0, st["caches"]


# ----------------------------------------------------- materialized staleness
@pytest.mark.parametrize("name", model_names())
def test_materialized_rows_never_stale(name):
    """Staleness property test: under a seeded random interleaving of
    {encode, param update, KG write, eviction pressure, version pin}, the
    cached-path encode is BITWISE a fresh no-cache compute, for every model
    family. A single served-stale row (old params or old KG version) would
    break the array equality."""
    kg = generate_synthetic_kg(80, 6, 600, seed=3)
    model, params = _model_params(kg, name=name)
    mat = MaterializedSubqueryCache(24)
    mat.watch_kg(kg)
    ex = PooledExecutor(model, b_max=32, mat_cache=mat)
    oracle = PooledExecutor(model, b_max=32)    # cache-free fresh compute
    pool = [s.query for s in OnlineSampler(kg, seed=11).sample_batch(40)]
    rng = np.random.default_rng(7)
    ops = ("encode", "encode", "param_update", "kg_write",
           "evict_pressure", "pin")
    for step in range(40):
        op = "encode" if step == 0 else ops[int(rng.integers(len(ops)))]
        if op == "encode":
            qs = [pool[i] for i in rng.integers(len(pool), size=8)]
            got = np.asarray(ex.encode(params, qs))
            want = np.asarray(oracle.encode(params, qs))
            np.testing.assert_array_equal(got, want)
        elif op == "param_update":
            params = {k: (v * np.float32(1.001)
                          if np.issubdtype(np.asarray(v).dtype, np.floating)
                          else v)
                      for k, v in params.items()}
            mat.bump_version("param_update")
        elif op == "kg_write":
            # add_triples notifies the watch_kg listener -> version bump
            v0 = mat.version
            kg.add_triples([[int(rng.integers(80)), int(rng.integers(6)),
                             int(rng.integers(80))]])
            assert mat.version == v0 + 1
        elif op == "evict_pressure":
            # encode more distinct queries than the 24-row budget holds
            idx = rng.choice(len(pool), size=30, replace=False)
            ex.encode(params, [pool[i] for i in idx])
        else:  # pin: inserts computed under a superseded version are dropped
            v = mat.version
            mat.bump_version("test_pin")
            stored = mat.insert([("bogus",)],
                                np.zeros((1, model.state_dim), np.float32),
                                version=v)
            assert stored == 0
            assert mat.lookup([("bogus",)]) == {}
    mat.check_consistent()
    st = mat.stats()
    assert st["invalidations"] > 0
    assert st["hits"] > 0          # the cache did serve rows, validly


def test_kg_write_invalidates_adjacency_views():
    """``add_triples`` must rebuild the CSR index and drop every cached
    adjacency view — a stale ``cached_property`` would quietly answer
    queries against the pre-write graph."""
    kg = generate_synthetic_kg(50, 4, 300, seed=1)
    deg0 = kg.out_degree.copy()
    n0 = len(kg)
    h = int(np.setdiff1d(np.arange(50), kg.triples[:, 0])[0]) \
        if len(np.setdiff1d(np.arange(50), kg.triples[:, 0])) else 0
    kg.add_triples([[h, 0, 1], [h, 0, 2]])
    assert len(kg) == n0 + 2
    assert kg.out_degree[h] == deg0[h] + 2
    assert set(kg.neighbors(h, 0)) >= {1, 2}
    assert kg.version == 1
    with pytest.raises(ValueError):
        kg.add_triples([[99, 0, 0]])    # entity out of range
    with pytest.raises(ValueError):
        kg.add_triples([[0, 9, 0]])     # relation out of range
    assert kg.version == 1              # failed writes don't bump


@pytest.mark.parametrize("name", model_names())
def test_pinned_graph_versions_replay_snapshot_oracle(name):
    """§LiveStore staleness property test: under a seeded interleaving of
    {pinned serve, unpinned serve, KG write, param update} against a LIVE
    engine (kg= attached, mat cache keyed by graph version), every served
    row equals the snapshot-pinned oracle — ``serve_batch`` run cache-free
    with the params that were live when the pinned version was admitted —
    for every model family. One row computed from the wrong params/version
    pairing breaks the equality."""
    from repro.launch.serve import serve_batch

    kg = generate_synthetic_kg(80, 6, 600, seed=3)
    model, params = _model_params(kg, name=name)
    mat = MaterializedSubqueryCache(32)
    mat.watch_kg(kg)
    bound = 3
    cfg = ServingConfig(max_batch=8, max_wait_ms=2.0, top_k=5,
                        max_staleness_versions=bound)
    pool = [s.query for s in OnlineSampler(kg, seed=11).sample_batch(30)]
    oracle_ex = PooledExecutor(model, b_max=32)     # cache-free fresh compute
    strip = lambda r: {k: v for k, v in r.items()   # noqa: E731
                       if k not in ("latency_ms", "batch_size")}
    params_at = {0: params}     # our own mirror of the engine's retention map
    cur = params
    rng = np.random.default_rng(13)
    ops = ("pinned", "pinned", "unpinned", "kg_write", "param_update")
    lagged = 0
    with ServingEngine(model, params, cfg=cfg, kg=kg, mat_cache=mat,
                       executor=PooledExecutor(model, b_max=32)) as eng:
        for step in range(16):
            op = "pinned" if step == 0 else ops[int(rng.integers(len(ops)))]
            if op == "kg_write":
                kg.add_triples([[int(rng.integers(80)), int(rng.integers(6)),
                                 int(rng.integers(80))]])
                # the engine's write listener registers the live params
                # under the new version; mirror that bookkeeping
                params_at[kg.graph_version] = cur
            elif op == "param_update":
                cur = {k: (v * np.float32(1.001)
                           if np.issubdtype(np.asarray(v).dtype, np.floating)
                           else v)
                       for k, v in cur.items()}
                eng.update_params(cur)
                params_at[kg.graph_version] = cur
            else:
                qs = [pool[i] for i in rng.integers(len(pool), size=4)]
                pin = None
                if op == "pinned":
                    # half the pins take the OLDEST admissible version so
                    # lagged replay is actually exercised, not just lag 0
                    lo = max(0, kg.graph_version - bound)
                    pin = (lo if rng.random() < 0.5
                           else int(rng.integers(lo, kg.graph_version + 1)))
                    lagged += int(pin < kg.graph_version)
                futs = [eng.submit(q, pin_version=pin) for q in qs]
                got = [strip(f.result(timeout=120)) for f in futs]
                oracle_params = params_at[pin if pin is not None
                                          else kg.graph_version]
                want, _ = serve_batch(model, oracle_params, oracle_ex, qs,
                                      top_k=5)
                assert got == [strip(w) for w in want]
        st = eng.stats()
    assert kg.graph_version > 0 and lagged > 0   # interleaving did exercise
    assert st["failures"] == 0 and st["stale_sheds"] == 0
    assert sum(st["version_lag_served"].values()) == st["completed"]


def test_insert_at_pinned_version_drops_after_bump():
    """The encode-under-old-params race, distilled: a batch snapshots
    (params, version), an update lands, its insert must be dropped whole."""
    mat = MaterializedSubqueryCache(8)
    rows = np.ones((2, 4), np.float32)
    v = mat.version
    assert mat.insert([("a",), ("b",)], rows, version=v) == 2
    assert len(mat.lookup([("a",), ("b",)])) == 2
    mat.bump_version("param_update")
    assert mat.insert([("c",)], rows[:1], version=v) == 0
    assert mat.stats()["stale_drops"] == 1
    assert mat.lookup([("c",)]) == {}
    # and the pre-bump rows are unservable at the new version
    assert mat.lookup([("a",), ("b",)]) == {}
