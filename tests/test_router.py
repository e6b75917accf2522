"""Multi-replica serving tier: affinity routing, tenant admission, hot swap
(DESIGN.md §ServingTier).

Property coverage the ISSUE pins:

* rendezvous assignment is deterministic per topology key and remaps at
  most ~1/N of topologies on replica join/leave;
* spill triggers ONLY above the queue-depth threshold, to ranked
  alternates only;
* tenant quotas and priority classes: low-priority excess is shed with a
  typed ``ShedError`` (never blocking), quotas release on completion;
* hot model swap: post-swap results are bitwise what a fresh pool started
  with the new params serves, and in-flight requests admitted pre-swap
  complete on the params they were admitted under;
* the ``tenant=``/``replica=`` metric labels land in the registry without
  touching the historical unlabeled keys.
"""
import queue as queue_mod
import threading
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro.launch.serve import serve_batch
from repro.models import ModelConfig, make_model
from repro.core.executor import PooledExecutor
from repro.obs.registry import get_registry
from repro.serving import (ReplicaPool, Router, RouterConfig, ServingConfig,
                           ServingEngine, ShedError, TenantSpec,
                           check_against_offline, make_workload,
                           query_topology_key, rendezvous_rank,
                           run_closed_loop, run_tenant_mix, TenantLoad)


@pytest.fixture(scope="module")
def served(tiny_kg):
    model = make_model("gqe", ModelConfig(dim=16, gamma=6.0))
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    return tiny_kg, model, params


def _oracle(model, params):
    ex = PooledExecutor(model, b_max=256)
    return lambda qs: serve_batch(model, params, ex, qs)[0]


# ---------------------------------------------------------------------------
# Rendezvous affinity properties
# ---------------------------------------------------------------------------

def test_topology_key_deterministic_and_binding_free(tiny_kg):
    qs = make_workload(tiny_kg, 40, seed=5)
    for q in qs:
        assert query_topology_key(q) == query_topology_key(q)
    # Affinity groups by POST-CSE shape: queries of one pattern share a
    # topology unless within-query merges (duplicate anchor/relation in two
    # branches) collapse the plan — then the merged shape is its own
    # topology, exactly as the schedule/plan/jit caches see it. Either way
    # the per-pattern topology set is tiny and binding-independent beyond
    # the merge structure.
    by_pattern = {}
    for q in qs:
        by_pattern.setdefault(q.pattern, []).append(query_topology_key(q))
    for pattern, topos in by_pattern.items():
        assert 1 <= len(set(topos)) <= 2, (pattern, set(topos))


def test_rendezvous_deterministic():
    topos = [(("t", i), (i,)) for i in range(50)]
    for topo in topos:
        r1 = rendezvous_rank(topo, [0, 1, 2, 3])
        r2 = rendezvous_rank(topo, [3, 2, 1, 0])  # order-insensitive
        assert r1 == r2
        assert sorted(r1) == [0, 1, 2, 3]


def test_rendezvous_remap_fraction_on_join_and_leave():
    topos = [((i, i + 1), (0,)) for i in range(200)]
    before = {t: rendezvous_rank(t, [0, 1, 2, 3])[0] for t in topos}
    # Join: adding replica 4 steals ~1/5 of topologies; NOTHING else moves.
    after_join = {t: rendezvous_rank(t, [0, 1, 2, 3, 4])[0] for t in topos}
    moved = [t for t in topos if before[t] != after_join[t]]
    assert all(after_join[t] == 4 for t in moved)
    assert len(moved) / len(topos) < 2 / 5  # ~1/5 expected, loose bound
    # Leave: removing replica 2 remaps exactly the topologies it owned.
    after_leave = {t: rendezvous_rank(t, [0, 1, 3])[0] for t in topos}
    for t in topos:
        if before[t] != 2:
            assert after_leave[t] == before[t]


# ---------------------------------------------------------------------------
# Spill + tenant admission against a stub pool (exact queue-depth control)
# ---------------------------------------------------------------------------

class StubReplica:
    def __init__(self):
        self.depth = 0
        self.full = False
        self.submitted = []

    def queue_depth(self):
        return self.depth

    def submit(self, q, top_k=None, timeout=None):
        if self.full:
            raise queue_mod.Full()
        f = Future()
        self.submitted.append((q, f, timeout))
        return f


class StubPool:
    def __init__(self, n):
        self._reps = {i: StubReplica() for i in range(n)}
        self.membership_token = 0

    def replicas(self):
        return dict(self._reps)

    def stats(self):
        return {}

    def update_params(self, params):
        pass

    def close(self, **kw):
        pass


@pytest.fixture
def stub_router(tiny_kg):
    pool = StubPool(4)
    router = Router(pool, tenants=[
        TenantSpec("gold", "high"),
        TenantSpec("bronze", "low"),
        TenantSpec("capped", "high", max_inflight=2),
    ], cfg=RouterConfig(spill_depth=4, spill_width=1))
    return pool, router, make_workload(tiny_kg, 30, seed=7)


def _primary(router, q):
    return router._ranking(router._topology(q))[0]


def test_no_spill_at_or_below_threshold(stub_router):
    pool, router, qs = stub_router
    q = qs[0]
    rid = _primary(router, q)
    pool._reps[rid].depth = 4  # == spill_depth: NOT above, no spill
    router.submit(q, tenant="gold")
    assert len(pool._reps[rid].submitted) == 1
    assert int(router._spilled) == 0


def test_spill_above_threshold_to_ranked_alternate(stub_router):
    pool, router, qs = stub_router
    q = qs[0]
    rank = router._ranking(router._topology(q))
    pool._reps[rank[0]].depth = 5  # above spill_depth=4
    router.submit(q, tenant="gold")
    assert len(pool._reps[rank[1]].submitted) == 1
    assert int(router._spilled) == 1
    # All alternates loaded too -> sticks with the affinity target (bounded
    # spill never sprays beyond spill_width ranked alternates).
    pool._reps[rank[1]].depth = 5
    router.submit(q, tenant="gold")
    assert len(pool._reps[rank[0]].submitted) == 1
    assert int(router._spilled) == 1


def test_low_priority_shed_never_blocks(stub_router):
    pool, router, qs = stub_router
    q = qs[0]
    rank = router._ranking(router._topology(q))
    # Loaded replica: shed by depth check before any enqueue attempt.
    for rid in rank[:2]:
        pool._reps[rid].depth = 5
    t0 = time.perf_counter()
    with pytest.raises(ShedError) as ei:
        router.submit(q, tenant="bronze")
    assert ei.value.reason == "backpressure"
    assert time.perf_counter() - t0 < 0.1
    # Full admission queue: the non-blocking enqueue converts queue.Full
    # into the same typed shed.
    for rid in rank[:2]:
        pool._reps[rid].depth = 0
        pool._reps[rid].full = True
    with pytest.raises(ShedError) as ei:
        router.submit(q, tenant="bronze")
    assert ei.value.reason == "backpressure"
    # High priority on the same loaded pool is admitted (blocking contract
    # delegated to the engine's bounded queue).
    for rid in rank[:2]:
        pool._reps[rid].full = False
        pool._reps[rid].depth = 5
    router.submit(q, tenant="gold")
    st = router.stats()
    assert st["tenants"]["bronze"]["shed"]["backpressure"] == 2
    assert st["tenants"]["bronze"]["completed"] == 0
    assert st["tenants"]["gold"]["submitted"] == 1


def test_quota_shed_and_release(stub_router):
    pool, router, qs = stub_router
    f1 = router.submit(qs[0], tenant="capped")
    router.submit(qs[1], tenant="capped")
    with pytest.raises(ShedError) as ei:
        router.submit(qs[2], tenant="capped")
    assert ei.value.reason == "quota"
    assert router.tenant_inflight("capped") == 2
    f1.set_result({"latency_ms": 1.0})
    assert router.tenant_inflight("capped") == 1
    router.submit(qs[3], tenant="capped")  # slot released
    assert router.stats()["tenants"]["capped"]["shed"]["quota"] == 1


def test_unknown_tenant_rejected(stub_router):
    _, router, qs = stub_router
    with pytest.raises(KeyError):
        router.submit(qs[0], tenant="nobody")


def test_membership_change_invalidates_ranking(stub_router):
    pool, router, qs = stub_router
    q = qs[0]
    r0 = router._ranking(router._topology(q))
    del pool._reps[r0[0]]
    pool.membership_token += 1
    r1 = router._ranking(router._topology(q))
    assert r0[0] not in r1 and r1 == [rid for rid in r0 if rid != r0[0]]


# ---------------------------------------------------------------------------
# Real pool: routing parity, hot swap, labels
# ---------------------------------------------------------------------------

def test_router_parity_with_offline_oracle(served):
    kg, model, params = served
    qs = make_workload(kg, 24, seed=9)
    pool = ReplicaPool(model, params, n_replicas=2,
                       cfg=ServingConfig(max_batch=8, max_wait_ms=2.0,
                                         record_batches=True),
                       mat_budget_rows=64)
    with Router(pool) as router:
        rep = run_closed_loop(router, qs, concurrency=8)
        assert all(r is not None for r in rep.results)
        serve_fn = _oracle(model, params)
        checked = sum(
            check_against_offline(r.engine.batch_log, serve_fn)
            for r in pool.replicas().values())
        assert checked >= len(qs)  # >= because of padding-free uniques


def test_hot_swap_matches_fresh_pool(served):
    kg, model, params = served
    params_b = model.init_params(jax.random.PRNGKey(7), kg.n_entities,
                                 kg.n_relations)
    qs = make_workload(kg, 24, seed=13)
    cfg = ServingConfig(max_batch=8, max_wait_ms=2.0, record_batches=True)
    pool = ReplicaPool(model, params, n_replicas=2, cfg=cfg,
                       mat_budget_rows=64)
    with Router(pool) as router:
        run_closed_loop(router, qs, concurrency=8)   # warm on old params
        router.update_params(params_b)               # hot swap, no drain
        pool.reset_counters(clear_log=True)
        after = run_closed_loop(router, qs, concurrency=8)
        # Every post-swap batch is bitwise the offline oracle on the NEW
        # params (composition-wise — the strongest form of "fresh pool").
        serve_fn = _oracle(model, params_b)
        for r in pool.replicas().values():
            check_against_offline(r.engine.batch_log, serve_fn)
    fresh = ReplicaPool(model, params_b, n_replicas=2, cfg=cfg,
                        mat_budget_rows=64)
    with Router(fresh) as router2:
        ref = run_closed_loop(router2, qs, concurrency=8)
    for got, want in zip(after.results, ref.results):
        assert got["top_entities"] == want["top_entities"]
        assert got["scores"] == want["scores"]


def test_inflight_pre_swap_served_on_admitted_params(served):
    kg, model, params = served
    params_b = model.init_params(jax.random.PRNGKey(11), kg.n_entities,
                                 kg.n_relations)
    qs = make_workload(kg, 16, seed=17)
    eng = ServingEngine(model, params, started=False,
                        cfg=ServingConfig(max_batch=8, max_wait_ms=2.0,
                                          pin_params_on_admit=True))
    try:
        pre = [eng.submit(q) for q in qs[:8]]     # admitted under params A
        eng.update_params(params_b)
        post = [eng.submit(q) for q in qs[8:]]    # admitted under params B
        eng.start()
        got_pre = [f.result(timeout=60) for f in pre]
        got_post = [f.result(timeout=60) for f in post]
    finally:
        eng.close()
    oracle_a = _oracle(model, params)(qs[:8])
    oracle_b = _oracle(model, params_b)(qs[8:])
    for got, want in zip(got_pre, oracle_a):
        assert got["top_entities"] == want["top_entities"]
        assert got["scores"] == want["scores"]
    for got, want in zip(got_post, oracle_b):
        assert got["top_entities"] == want["top_entities"]
        assert got["scores"] == want["scores"]
    assert eng.stats()["params_version"] == 1


def test_default_engine_has_no_params_version_key(served):
    kg, model, params = served
    eng = ServingEngine(model, params, started=False)
    try:
        assert "params_version" not in eng.stats()
    finally:
        eng.close(drain=False)


def test_pin_params_rejects_sem_cache_and_kg(served):
    kg, model, params = served
    cfg = ServingConfig(pin_params_on_admit=True)
    with pytest.raises(ValueError):
        ServingEngine(model, params, cfg=cfg, kg=kg, started=False)


def test_tenant_and_replica_metric_labels(served):
    kg, model, params = served
    qs = make_workload(kg, 16, seed=19)
    pool = ReplicaPool(model, params, n_replicas=2,
                       cfg=ServingConfig(max_batch=8, max_wait_ms=2.0))
    router = Router(pool, tenants=[TenantSpec("gold", "high"),
                                   TenantSpec("bronze", "low")])
    # A live single-engine (unlabeled) instance alongside the tier: its keys
    # must stay the historical unlabeled ones, unpolluted by the labels.
    plain = ServingEngine(model, params, started=False)
    with router:
        reports = run_tenant_mix(router, [
            TenantLoad("gold", qs[:8], qps=0.0),
            TenantLoad("bronze", qs[8:], qps=0.0),
        ])
        snap = get_registry().snapshot()
    plain.close(drain=False)
    assert reports["gold"].completed == 8
    assert reports["gold"].failures == 0
    # New labeled keys exist...
    assert snap.get("serving_submitted{tenant=gold}", 0) == 8
    assert "serving_latency_ms{tenant=gold}_count" in snap
    assert "serving_shed{reason=backpressure,tenant=bronze}" in snap
    assert any(k.startswith("serving_batches{replica=") for k in snap)
    # ...and the historical unlabeled keys still do (single-engine path),
    # with the labeled tier traffic NOT aliasing into them.
    assert snap.get("serving_submitted") == 0


_DEEP_PATTERNS = ["3p", "3i", "ip", "pi", "inp", "pin", "pni", "up", "3in"]


def _lockstep_replay(router, blocks):
    """One block in flight at a time: each block is one micro-batch on its
    home replica, so every replay forms the same compositions."""
    for blk in blocks:
        for f in router.submit_many(blk):
            f.result(timeout=120)


def test_affinity_replay_serves_each_replica_from_its_cache(served):
    """Deep queries partitioned by rendezvous over 4 replicas, each
    replica's share within its materialized-row budget and the union
    beyond it: after a warm pass, a replay through the pool compiles
    nothing and serves every row from the home replica's cache, while one
    replica with the same budget serves none from its cache."""
    kg, model, params = served
    batch, rids = 8, [0, 1, 2, 3]
    uniq = {q.key(): q for q in make_workload(kg, 96, seed=13,
                                              patterns=_DEEP_PATTERNS)}
    streams = {rid: [] for rid in rids}
    for q in uniq.values():
        streams[rendezvous_rank(query_topology_key(q), rids)[0]].append(q)
    shares = [qs[:len(qs) // batch * batch] for qs in streams.values()]
    blocks = [qs[i:i + batch] for qs in shares
              for i in range(0, len(qs), batch)]
    budget = max(map(len, shares)) + batch
    assert sum(map(len, blocks)) >= budget + 2 * batch  # union overflows
    cfg = ServingConfig(max_batch=batch, max_wait_ms=2000.0)
    hit_rates = {}
    for n in (len(rids), 1):
        pool = ReplicaPool(model, params, n_replicas=n, cfg=cfg,
                           mat_budget_rows=budget)
        with Router(pool, cfg=RouterConfig(spill_width=0)) as router:
            _lockstep_replay(router, blocks)
            pool.reset_counters()
            _lockstep_replay(router, blocks)
            stats = {rid: r.stats() for rid, r in pool.replicas().items()}
        assert all(st["retraces"] == 0 for st in stats.values()), stats
        hit_rates[n] = [st["mat_cache"]["hit_rate"] for st in stats.values()
                        if st["submitted"]]
    assert hit_rates[len(rids)] == [1.0] * len(rids)
    assert hit_rates[1] == [0.0]


def test_overload_sheds_only_low_priority_on_a_real_pool(served):
    """High-priority work queued on stopped replicas: a low-priority flood
    is shed at once with typed, counted ``ShedError``s that are not
    failures; once the replicas start, every admitted request of both
    tenants is served."""
    kg, model, params = served
    qs = make_workload(kg, 24, seed=21)
    pool = ReplicaPool(model, params, n_replicas=2, started=False,
                       cfg=ServingConfig(max_batch=8, max_wait_ms=2.0))
    router = Router(pool, tenants=[TenantSpec("gold", "high"),
                                   TenantSpec("bronze", "low")],
                    cfg=RouterConfig(spill_width=0, low_priority_depth=1))
    with router:
        gold = [router.submit(q, tenant="gold") for q in qs[:8]]
        bronze, sheds = [], 0
        for q in qs[8:]:
            t0 = time.perf_counter()
            try:
                bronze.append(router.submit(q, tenant="bronze"))
            except ShedError as e:
                assert e.reason == "backpressure"
                sheds += 1
            assert time.perf_counter() - t0 < 0.1  # never blocks
        for rep in pool.replicas().values():
            rep.start()
        for f in gold + bronze:
            assert f.result(timeout=120)["top_entities"]
        st = router.stats()["tenants"]
    assert sheds > 0
    assert st["bronze"]["shed"]["backpressure"] == sheds
    assert (st["bronze"]["completed"], st["bronze"]["failures"]) == (
        len(bronze), 0)
    assert st["gold"]["completed"] == 8 and st["gold"]["failures"] == 0
    assert sum(st["gold"]["shed"].values()) == 0
