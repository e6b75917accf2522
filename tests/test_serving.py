"""Serving subsystem tests: the continuous-batching engine (flush policy,
bucketing, backpressure, futures/latency, error isolation, out-of-core
serving) and the ``serve_batch`` offline baseline (single-trace regression,
engine parity)."""
import queue
import time

import jax
import numpy as np
import pytest

from repro.core import PooledExecutor
from repro.launch.serve import serve_batch
from repro.core.patterns import QueryInstance
from repro.models import ModelConfig, make_model
from repro.semantic import SemanticCache
from repro.serving import (ServingConfig, ServingEngine,
                           check_against_offline, make_workload,
                           pad_to_bucket, run_closed_loop, run_open_loop,
                           scorer_for)


def _setup(tiny_kg, name="gqe", dim=8, seed=0, **cfg_kw):
    model = make_model(name, ModelConfig(dim=dim, **cfg_kw))
    params = model.init_params(jax.random.PRNGKey(seed), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    return model, params, PooledExecutor(model, b_max=64)


# ---------------------------------------------------------------- satellites
def test_serve_batch_traces_score_all_exactly_once(tiny_kg, mixed_queries):
    """Regression for the historical bug: ``serve_batch`` rebuilt
    ``jax.jit(model.score_all)`` per call, so EVERY batch retraced. The
    process-wide cached scorer must trace once across repeated calls."""
    # dim=12 gives this test its own scorer-cache key, so traces from other
    # tests sharing the default dim can't mask a regression here.
    model, params, ex = _setup(tiny_kg, dim=12)
    queries = [b.query for b in mixed_queries][:8]
    scorer = scorer_for(model)
    t0 = scorer.traces
    first, _ = serve_batch(model, params, ex, queries, top_k=5)
    for _ in range(3):
        again, _ = serve_batch(model, params, ex, queries, top_k=5)
        assert again == first  # deterministic replay, same compiled programs
    assert scorer.traces - t0 == 1
    # and the encode side compiled once per signature too
    assert ex.cache_stats()["encode_jit"]["misses"] == 1


def test_scorer_cache_shared_across_instances(tiny_kg):
    """Two instances of the same zoo family share one compiled scorer."""
    m1, p1, _ = _setup(tiny_kg, dim=12)
    m2, p2, _ = _setup(tiny_kg, dim=12, seed=1)
    assert scorer_for(m1) is scorer_for(m2)


def test_pad_to_bucket():
    t = QueryInstance("1p", np.array([0]), np.array([0]))
    for n, want in [(1, 1), (2, 2), (3, 4), (5, 8), (8, 8), (9, 16)]:
        padded, n_real = pad_to_bucket([t] * n)
        assert (len(padded), n_real) == (want, n)
        assert all(p is t for p in padded)
    assert pad_to_bucket([]) == ([], 0)


# -------------------------------------------------------------------- engine
def test_engine_matches_offline_serve_batch(tiny_kg, mixed_queries):
    """Closed-loop traffic through the engine == offline serve_batch on the
    same recorded micro-batch compositions, bit for bit."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=8, max_wait_ms=1000.0, top_k=7,
                        record_batches=True)
    with ServingEngine(model, params, executor=ex, cfg=cfg) as engine:
        queries = [b.query for b in mixed_queries][:24]
        rep = run_closed_loop(engine, queries, concurrency=8)
        assert [r["pattern"] for r in rep.results] == [q.pattern for q in queries]
        log = list(engine.batch_log)
    # fresh executor: the oracle must not reuse the engine's compiled cache
    ex2 = PooledExecutor(model, b_max=64)
    checked = check_against_offline(
        log, lambda qs: serve_batch(model, params, ex2, qs, top_k=7)[0])
    assert checked == 24


def test_engine_mixed_top_k_matches_per_k_oracle(tiny_kg, mixed_queries):
    """Co-batched requests with different top_k each match serve_batch at
    THEIR OWN k (selection at k, not a sliced k_max selection — the two can
    disagree on boundary-tied scores)."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=8, max_wait_ms=1000.0, record_batches=True)
    ks = [3, 9]
    with ServingEngine(model, params, executor=ex, cfg=cfg) as engine:
        queries = [b.query for b in mixed_queries][:8]
        futs = [engine.submit(q, top_k=ks[i % 2])
                for i, q in enumerate(queries)]
        results = [f.result(timeout=60) for f in futs]
        log = list(engine.batch_log)
    assert [len(r["top_entities"]) for r in results] == [ks[i % 2]
                                                         for i in range(8)]
    ex2 = PooledExecutor(model, b_max=64)
    for rec in log:
        oracles = {k: serve_batch(model, params, ex2, rec.queries,
                                  top_k=k)[0] for k in ks}
        for i, got in enumerate(rec.results[: rec.n_real]):
            want = oracles[len(got["top_entities"])][i]
            assert got["top_entities"] == want["top_entities"]
            assert got["scores"] == want["scores"]


def test_engine_rejects_nonpositive_top_k(tiny_kg, mixed_queries):
    model, params, ex = _setup(tiny_kg)
    engine = ServingEngine(model, params, executor=ex, started=False)
    with pytest.raises(ValueError, match="top_k"):
        engine.submit(mixed_queries[0].query, top_k=0)
    engine.close(drain=False)


def test_engine_age_flush_pads_partial_batch(tiny_kg, mixed_queries):
    """A partial batch must flush once the oldest request ages out, padded
    to the pow2 bucket, and padded rows must not leak into results."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=16, max_wait_ms=30.0, record_batches=True)
    with ServingEngine(model, params, executor=ex, cfg=cfg) as engine:
        queries = [b.query for b in mixed_queries][:5]
        futs = engine.submit_many(queries)
        results = [f.result(timeout=60) for f in futs]
        st = engine.stats()
        log = list(engine.batch_log)
    assert len(results) == 5
    total_real = sum(r.n_real for r in log)
    assert total_real == 5
    for rec in log:
        assert len(rec.queries) == 1 << (rec.n_real - 1).bit_length()
        assert len(rec.results) == rec.n_real
    assert st["flushes"]["age"] >= 1
    assert st["flushes"]["size"] == 0


def test_engine_bounded_admission_backpressure(tiny_kg, mixed_queries):
    """With the batcher stopped, the admission queue fills to queue_depth
    and further submits raise queue.Full; once started, all complete."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=4, max_wait_ms=5.0, queue_depth=3)
    engine = ServingEngine(model, params, executor=ex, cfg=cfg, started=False)
    queries = [b.query for b in mixed_queries][:4]
    futs = [engine.submit(q) for q in queries[:3]]
    with pytest.raises(queue.Full):
        engine.submit(queries[3], timeout=0.05)
    engine.start()
    for f in futs:
        assert f.result(timeout=60)["top_entities"]
    engine.close()
    with pytest.raises(RuntimeError):
        engine.submit(queries[0])


def test_engine_isolates_poison_request(tiny_kg, mixed_queries):
    """One malformed query fails its own future; co-batched neighbors and
    later traffic still serve."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=4, max_wait_ms=50.0)
    with ServingEngine(model, params, executor=ex, cfg=cfg) as engine:
        good = [b.query for b in mixed_queries][:3]
        bad = QueryInstance("no-such-pattern", np.array([0]), np.array([0]))
        futs = engine.submit_many(good[:2] + [bad])
        assert futs[0].result(timeout=60)["top_entities"]
        assert futs[1].result(timeout=60)["top_entities"]
        with pytest.raises(KeyError):
            futs[2].result(timeout=60)
        assert engine.submit(good[2]).result(timeout=60)["top_entities"]
        assert engine.stats()["failures"] == 1


def test_engine_zero_steady_state_retraces_on_replay(tiny_kg):
    """Replaying a deterministic workload after warmup compiles nothing."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=8, max_wait_ms=1000.0)
    with ServingEngine(model, params, executor=ex, cfg=cfg) as engine:
        workload = make_workload(tiny_kg, 32, seed=5)
        run_closed_loop(engine, workload, concurrency=8)
        assert engine.retraces() > 0  # warmup did compile
        engine.reset_counters()
        rep = run_open_loop(engine, workload)  # burst: same chunkings
        assert engine.retraces() == 0, engine.stats()["caches"]
        lat = engine.stats()["latency_ms"]
    assert lat["n"] == 32
    assert lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    assert all(r["latency_ms"] > 0 for r in rep.results)
    assert all(r["batch_size"] == 8 for r in rep.results)
    assert rep.offered_qps > 0  # the arrival rate it measured


def test_engine_closed_loop_replay_compiles_nothing(tiny_kg):
    """A closed-loop replay of the warm-up workload, then an open-loop
    burst of it, both compile nothing, and every computed row matches the
    offline ``serve_batch`` on the logged compositions."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=8, max_wait_ms=1000.0, record_batches=True)
    workload = make_workload(tiny_kg, 32, seed=11)
    with ServingEngine(model, params, executor=ex, cfg=cfg) as engine:
        run_closed_loop(engine, workload, concurrency=8)
        engine.reset_counters(clear_log=True)
        run_closed_loop(engine, workload, concurrency=8)
        run_open_loop(engine, workload)
        assert engine.retraces() == 0, engine.stats()["caches"]
        log = list(engine.batch_log)
    ex2 = PooledExecutor(model, b_max=64)
    assert check_against_offline(
        log, lambda qs: serve_batch(model, params, ex2, qs)[0]) == 64


def test_engine_out_of_core_semantic_serving(tiny_kg, mixed_queries, rng):
    """Semantic serving through the engine — hot-set staging on the batcher
    thread + chunked store-streamed scoring — matches offline serve_batch
    with the same cache/chunked-scorer configuration, bit for bit, even
    with a budget small enough to force evictions."""
    d_l = 16
    table = rng.normal(size=(tiny_kg.n_entities, d_l)).astype(np.float32)
    rows_fn = lambda ids: table[np.asarray(ids, dtype=np.int64).ravel()]  # noqa: E731

    model = make_model("gqe", ModelConfig(dim=8, semantic_dim=d_l))
    ex = PooledExecutor(model, b_max=64)
    cache = SemanticCache(table, budget_rows=48)
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations, semantic_cache=cache)
    cfg = ServingConfig(max_batch=8, max_wait_ms=1000.0, top_k=6,
                        record_batches=True)
    with ServingEngine(model, params, executor=ex, cfg=cfg,
                       sem_cache=cache, sem_rows_fn=rows_fn) as engine:
        queries = [b.query for b in mixed_queries][:24]
        run_closed_loop(engine, queries, concurrency=8)
        log = list(engine.batch_log)
        assert engine.stats()["sem_cache"]["rows_staged"] > 0

    # offline oracle: fresh cache + params, same chunked scorer; params
    # thread through the closure because staging rewrites them per batch
    cache2 = SemanticCache(table, budget_rows=48)
    params2 = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                                tiny_kg.n_relations, semantic_cache=cache2)
    ex2 = PooledExecutor(model, b_max=64)
    chunked = lambda p, q: model.score_all_chunked(p, q, rows_fn, chunk=64)  # noqa: E731

    def oracle(qs):
        nonlocal params2
        res, params2 = serve_batch(model, params2, ex2, qs, top_k=6,
                                   score_all_fn=chunked, sem_cache=cache2)
        return res

    assert check_against_offline(log, oracle) == 24


def test_engine_requires_rows_fn_with_cache(tiny_kg, rng):
    table = rng.normal(size=(tiny_kg.n_entities, 16)).astype(np.float32)
    model = make_model("gqe", ModelConfig(dim=8, semantic_dim=16))
    cache = SemanticCache(table, budget_rows=32)
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations, semantic_cache=cache)
    with pytest.raises(ValueError, match="sem_rows_fn"):
        ServingEngine(model, params, sem_cache=cache, started=False)
    # same contract offline: cache params can't dense-score, so serve_batch
    # must refuse sem_cache without a chunked score_all_fn BEFORE staging
    ex = PooledExecutor(model, b_max=64)
    q = QueryInstance("1p", np.array([0]), np.array([0]))
    with pytest.raises(ValueError, match="score_all_fn"):
        serve_batch(model, params, ex, [q], sem_cache=cache)


def test_engine_coalesces_duplicate_inflight_requests(tiny_kg, mixed_queries):
    """Exact-duplicate in-flight requests (same ``QueryInstance.key()``)
    share one computed row: every future resolves, results are identical,
    the batch log records the UNIQUE composition, and ``stats()['coalesced']``
    counts the deduped requests."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=10, max_wait_ms=1000.0, top_k=5,
                        record_batches=True)
    distinct = [b.query for b in mixed_queries][:2]
    dup = mixed_queries[2].query
    engine = ServingEngine(model, params, executor=ex, cfg=cfg, started=False)
    futs = engine.submit_many([dup] * 8 + distinct)
    engine.start()
    results = [f.result(timeout=60) for f in futs]
    st = engine.stats()
    log = list(engine.batch_log)
    engine.close()
    assert st["coalesced"] == 7
    assert all(r["top_entities"] == results[0]["top_entities"] and
               r["scores"] == results[0]["scores"] for r in results[:8])
    [rec] = log
    assert rec.n_real == 3                      # 3 unique queries computed
    assert len(rec.queries) == 4                # padded to pow2 of uniques
    assert [q.key() for q in rec.queries[:3]] == [
        dup.key(), distinct[0].key(), distinct[1].key()]
    # the unique composition replays bit-identically through serve_batch
    ex2 = PooledExecutor(model, b_max=64)
    assert check_against_offline(
        log, lambda qs: serve_batch(model, params, ex2, qs, top_k=5)[0]) == 3


def test_engine_coalesced_duplicates_honor_per_request_top_k(tiny_kg,
                                                             mixed_queries):
    """Duplicates with DIFFERENT top_k still share the computed row — only
    the final selection differs."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=4, max_wait_ms=1000.0, top_k=9,
                        record_batches=True)
    q = mixed_queries[0].query
    engine = ServingEngine(model, params, executor=ex, cfg=cfg, started=False)
    f3 = engine.submit(q, top_k=3)
    f9 = engine.submit(q)          # engine default k=9
    engine.start()
    r3, r9 = f3.result(timeout=60), f9.result(timeout=60)
    st = engine.stats()
    [rec] = engine.batch_log
    engine.close()
    # the logged row records the DEFAULT-k selection (fixed-k oracle replay
    # contract), even though the custom-k request was submitted first
    assert len(rec.results[0]["top_entities"]) == 9
    assert st["coalesced"] == 1
    assert len(r3["top_entities"]) == 3 and len(r9["top_entities"]) == 9
    # Same underlying score row: the k=3 score sequence prefixes the k=9
    # one. (Ids are not asserted — argpartition may arbitrate boundary-TIED
    # scores differently between the two selections.)
    assert r3["scores"] == r9["scores"][:3]


def test_engine_concurrent_submitters_share_caches(tiny_kg, mixed_queries):
    """N submitter threads + the batcher + outside prepare() callers share
    the plan and materialized caches concurrently: every good future
    resolves, each poison request fails ALONE (KeyError, solo-retry
    isolation), counters sum exactly, the cache invariants hold (no torn
    slot maps) and the engine stays serviceable afterwards."""
    import threading

    from repro.core import MaterializedSubqueryCache

    model, params, ex = _setup(tiny_kg)
    mat = MaterializedSubqueryCache(32)
    cfg = ServingConfig(max_batch=8, max_wait_ms=5.0)
    pool = [b.query for b in mixed_queries][:6]
    bad = QueryInstance("no-such-pattern", np.array([0]), np.array([0]))
    n_threads, per_thread = 4, 25
    n_poison_each = sum(1 for i in range(per_thread) if i % 12 == 7)
    results, errors = [], []
    res_lock = threading.Lock()
    with ServingEngine(model, params, executor=ex, cfg=cfg,
                       mat_cache=mat) as engine:

        def submitter(tid):
            rng = np.random.default_rng(tid)
            futs = []
            for i in range(per_thread):
                q = bad if i % 12 == 7 else pool[int(rng.integers(len(pool)))]
                futs.append((q is bad, engine.submit(q)))
            for is_bad, f in futs:
                try:
                    r = f.result(timeout=120)
                    with res_lock:
                        results.append((is_bad, r))
                except KeyError:
                    with res_lock:
                        errors.append(is_bad)

        def preparer():
            # hammer the shared plan cache from OUTSIDE the batcher thread
            for _ in range(40):
                ex.prepare(pool)

        threads = ([threading.Thread(target=submitter, args=(t,))
                    for t in range(n_threads)]
                   + [threading.Thread(target=preparer) for _ in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # poison must not have wedged any lock: the engine still serves
        assert engine.submit(pool[0]).result(timeout=60)["top_entities"]
        st = engine.stats()

    n_poison = n_threads * n_poison_each
    assert errors == [True] * n_poison          # every poison future raised
    assert len(results) == n_threads * per_thread - n_poison
    assert not any(is_bad for is_bad, _ in results)
    assert st["failures"] == n_poison
    assert st["completed"] == st["submitted"]
    mat.check_consistent()
    mc = st["mat_cache"]
    assert mc["hits"] + mc["misses"] > 0
    assert mc["hits"] > 0                       # dup-heavy pool did reuse rows


def test_engine_drain_on_close(tiny_kg, mixed_queries):
    """close(drain=True) serves everything already admitted — the tail
    partial batch flushes immediately, not after the age window."""
    model, params, ex = _setup(tiny_kg)
    cfg = ServingConfig(max_batch=16, max_wait_ms=10_000.0)
    engine = ServingEngine(model, params, executor=ex, cfg=cfg)
    futs = engine.submit_many([b.query for b in mixed_queries][:3])
    t0 = time.perf_counter()
    engine.close(drain=True)
    assert time.perf_counter() - t0 < 10  # did not sit out max_wait_ms
    for f in futs:
        assert f.result(timeout=1)["top_entities"]
    assert engine.stats()["flushes"]["drain"] == 1
