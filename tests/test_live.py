"""Live-write subsystem (DESIGN.md §LiveStore): SemanticStore in-place
growth, staleness-bounded serving with version pinning, entity-table growth,
and the LiveNGDB write coordinator with background incremental fine-tuning."""
import os
import threading

import jax
import numpy as np
import pytest

from repro.core import PooledExecutor
from repro.data import KnowledgeGraph, generate_synthetic_kg
from repro.launch.serve import serve_batch
from repro.core.patterns import QueryInstance
from repro.models import ModelConfig, make_model
from repro.semantic import SemanticStore, SemanticStoreWriter
from repro.serving import (LiveNGDB, ServingConfig, ServingEngine,
                           StaleVersionError, WriteReceipt, grow_entity_rows)
from repro.training.loop import incremental_finetune


def _store(tmp_path, rows, *, quant="fp32", shard_rows=4, name="s"):
    d = str(tmp_path / name)
    w = SemanticStoreWriter(d, dim=rows.shape[1], quant=quant,
                            shard_rows=shard_rows)
    w.append(rows.astype(np.float32))
    w.finalize()
    return SemanticStore(d)


def _fresh_setup(name="gqe", dim=8, seed=0, n_entities=60, **cfg_kw):
    """Per-test KG (live-write tests mutate it — never share tiny_kg)."""
    kg = generate_synthetic_kg(n_entities, 4, 300, seed=3)
    model = make_model(name, ModelConfig(dim=dim, gamma=6.0, **cfg_kw))
    params = model.init_params(jax.random.PRNGKey(seed), kg.n_entities,
                               kg.n_relations)
    return kg, model, params, PooledExecutor(model, b_max=64)


def _fresh_rows(kg, n, seed=0):
    """n triples guaranteed absent from kg (valid ids)."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        cand = np.stack([rng.integers(0, kg.n_entities, 4 * n),
                         rng.integers(0, kg.n_relations, 4 * n),
                         rng.integers(0, kg.n_entities, 4 * n)], axis=1)
        cand = cand[~kg.contains(cand)]
        out += [row for row in np.unique(cand, axis=0)]
    return np.array(out[:n])


def _payload(result):
    """Drop per-request timing fields; keep the served content."""
    return {k: v for k, v in result.items()
            if k not in ("latency_ms", "batch_size")}


def _queries(kg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    heads = kg.triples[rng.integers(0, len(kg), n), 0]
    rels = kg.triples[rng.integers(0, len(kg), n), 1]
    return [QueryInstance("1p", np.array([h]), np.array([r]))
            for h, r in zip(heads, rels)]


# ----------------------------------------------------------- store growth
@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_store_append_rows_roundtrip(tmp_path, rng, quant):
    """append_rows merges into a ragged last shard + spills fresh shards;
    OLD rows stay bitwise what the store already served for them."""
    base = rng.normal(size=(10, 8)).astype(np.float32)   # 2 full + 1 ragged
    extra = rng.normal(size=(9, 8)).astype(np.float32)
    store = _store(tmp_path, base, quant=quant, name=quant)
    before = store.read_rows(np.arange(10))
    got = store.append_rows(extra)
    assert got == range(10, 19)
    assert store.n_rows == 19
    # uniform geometry: every shard but the last holds exactly shard_rows
    reopened = SemanticStore(str(tmp_path / quant))
    assert reopened.n_rows == 19
    np.testing.assert_array_equal(store.read_rows(np.arange(10)), before)
    np.testing.assert_array_equal(reopened.read_rows(np.arange(10)), before)
    if quant == "fp32":
        np.testing.assert_array_equal(
            reopened.read_rows(np.arange(10, 19)), extra)
    else:
        got = reopened.read_rows(np.arange(10, 19))
        bound = np.abs(extra).max(axis=1, keepdims=True) / 254.0 + 1e-7
        assert (np.abs(got - extra) <= bound).all()


def test_store_append_crash_safe(tmp_path, rng, monkeypatch):
    """Crash between shard writes and the meta publish must leave the OLD
    store fully openable with its old rows bitwise intact."""
    import repro.semantic.store as store_mod

    base = rng.normal(size=(10, 8)).astype(np.float32)
    store = _store(tmp_path, base, name="crash")
    before = store.read_rows(np.arange(10))
    real = store_mod._write_atomic

    def boom(path, payload):
        if path.endswith("meta.json"):
            raise OSError("simulated crash before meta publish")
        real(path, payload)

    monkeypatch.setattr(store_mod, "_write_atomic", boom)
    with pytest.raises(OSError, match="simulated crash"):
        store.append_rows(rng.normal(size=(7, 8)).astype(np.float32))
    monkeypatch.setattr(store_mod, "_write_atomic", real)
    reopened = SemanticStore(str(tmp_path / "crash"))
    assert reopened.n_rows == 10          # append never became visible
    np.testing.assert_array_equal(reopened.read_rows(np.arange(10)), before)
    # and the surviving in-memory store still works + can retry the append
    assert store.n_rows == 10
    store.append_rows(rng.normal(size=(7, 8)).astype(np.float32))
    assert SemanticStore(str(tmp_path / "crash")).n_rows == 17


# ----------------------------------------------------------- params growth
def test_grow_entity_rows_claims_padding_first():
    model = make_model("gqe", ModelConfig(dim=8, entity_pad=8))
    params = model.init_params(jax.random.PRNGKey(0), 10, 4)
    assert params["entity"].shape[0] == 16  # padded
    ent = params["entity"]
    grown = grow_entity_rows(model, params, 3)
    assert model.n_entities == 13
    assert grown["entity"] is ent           # pad rows claimed, no realloc
    grown2 = grow_entity_rows(model, grown, 5)  # 18 > 16 -> realloc to 24
    assert model.n_entities == 18
    assert grown2["entity"].shape[0] == 24
    np.testing.assert_array_equal(np.asarray(grown2["entity"][:16]),
                                  np.asarray(ent))


def test_grow_entity_rows_sem_table():
    model = make_model("gqe", ModelConfig(dim=8, semantic_dim=4))
    table = np.arange(10 * 4, dtype=np.float32).reshape(10, 4)
    params = model.init_params(jax.random.PRNGKey(0), 10, 4,
                               semantic_table=table)
    with pytest.raises(ValueError, match="sem_rows"):
        grow_entity_rows(model, params, 2)
    new_rows = np.full((2, 4), 7.0, np.float32)
    grown = grow_entity_rows(model, params, 2, sem_rows=new_rows)
    np.testing.assert_array_equal(np.asarray(grown["sem_table"][:10]), table)
    np.testing.assert_array_equal(np.asarray(grown["sem_table"][10:12]),
                                  new_rows)


def test_grow_entity_rows_rejects_hot_set_layout():
    model = make_model("gqe", ModelConfig(dim=8))
    model.n_entities = 10
    params = {"entity": np.zeros((10, 8), np.float32),
              "sem_slot": np.zeros(10, np.int32)}
    with pytest.raises(NotImplementedError, match="hot set"):
        grow_entity_rows(model, params, 2)


# --------------------------------------------------- staleness-bounded serving
def test_stale_pin_is_shed_with_typed_error():
    kg, model, params, ex = _fresh_setup()
    cfg = ServingConfig(max_batch=8, max_wait_ms=5.0, top_k=5,
                        max_staleness_versions=1)
    with ServingEngine(model, params, executor=ex, cfg=cfg, kg=kg) as eng:
        q = _queries(kg, 1)[0]
        assert eng.submit(q, pin_version=0).result(timeout=30)["pattern"] == "1p"
        for row in _fresh_rows(kg, 2):          # two separate version bumps
            kg.add_triples(row[None])
        assert eng.graph_version == 2
        with pytest.raises(StaleVersionError) as ei:
            eng.submit(q, pin_version=0)
        assert (ei.value.pinned, ei.value.current, ei.value.bound) == (0, 2, 1)
        eng.submit(q, pin_version=1).result(timeout=30)  # within bound: served
        with pytest.raises(ValueError, match="unknown graph version"):
            eng.submit(q, pin_version=99)
        st = eng.stats()
    assert st["stale_sheds"] == 1 and st["failures"] == 0
    assert st["graph_version"] == 2
    assert st["version_lag_served"] == {0: 1, 1: 1}


def test_pin_version_requires_kg(tiny_kg):
    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    cfg = ServingConfig(max_batch=4, max_wait_ms=5.0)
    with ServingEngine(model, params, executor=PooledExecutor(model, b_max=64),
                       cfg=cfg) as eng:
        with pytest.raises(ValueError, match="live graph"):
            eng.submit(_queries(tiny_kg, 1)[0], pin_version=0)


def test_pinned_replay_bit_identical_through_writes():
    """A pin at version v must keep serving the v-era params verbatim while
    writes + param updates land — bitwise equal to the offline oracle run
    on the admitted snapshot's params."""
    kg, model, params, ex = _fresh_setup()
    cfg = ServingConfig(max_batch=8, max_wait_ms=5.0, top_k=5,
                        max_staleness_versions=4)
    qs = _queries(kg, 6)
    with ServingEngine(model, params, executor=ex, cfg=cfg, kg=kg) as eng:
        first = [_payload(eng.submit(q, pin_version=0).result(timeout=30))
                 for q in qs]
        # graph write + a params publish (as online training would do)
        kg.add_triples(np.array([[2, 0, 3], [2, 1, 4]]))
        bumped = dict(eng.params)
        bumped["entity"] = eng.params["entity"] * 1.5
        eng.update_params(bumped)
        unpinned = [_payload(eng.submit(q).result(timeout=30)) for q in qs]
        replay = [_payload(eng.submit(q, pin_version=0).result(timeout=30))
                  for q in qs]
    assert replay == first                      # pinned replay is frozen
    assert unpinned != first                    # fresh params actually differ
    oracle, _ = serve_batch(model, params, PooledExecutor(model, b_max=64),
                            qs, top_k=5)
    for got, want in zip(first, oracle):
        assert got == _payload(want)


# ------------------------------------------------------------------ LiveNGDB
def test_live_ngdb_write_burst_serving_continuity():
    kg, model, params, ex = _fresh_setup()
    cfg = ServingConfig(max_batch=8, max_wait_ms=2.0, top_k=5,
                        max_staleness_versions=8)
    qs = _queries(kg, 4)
    with ServingEngine(model, params, executor=ex, cfg=cfg, kg=kg) as eng:
        with LiveNGDB(model, kg, eng, finetune_steps=2, seed=0) as live:
            futures = []
            for k in range(6):
                futures += [eng.submit(q) for q in qs]
                r = live.write(np.array([[k, 0, (k + 7) % kg.n_entities],
                                         [k, 1, (k + 9) % kg.n_entities]]))
                assert isinstance(r, WriteReceipt)
            for f in futures:
                assert f.result(timeout=60)["pattern"] == "1p"
            live.flush()
            n_fresh = sum(1 for r in live.receipts if r.n_written)
            assert live.finetunes_done == n_fresh > 0
            # duplicate burst: no version bump, nothing enqueued
            v = kg.graph_version
            done = live.finetunes_done
            prior = next(r for r in live.receipts if r.n_written)
            r = live.write(prior.fresh_triples)
            assert r.n_written == 0 and kg.graph_version == v
            live.flush()
            assert live.finetunes_done == done
            st = eng.stats()
    assert st["failures"] == 0 and st["stale_sheds"] == 0
    assert st["graph_version"] == kg.graph_version


def test_live_ngdb_entity_growth_end_to_end():
    kg, model, params, ex = _fresh_setup()
    n0 = kg.n_entities
    cfg = ServingConfig(max_batch=8, max_wait_ms=2.0, top_k=5,
                        max_staleness_versions=8)
    with ServingEngine(model, params, executor=ex, cfg=cfg, kg=kg) as eng:
        with LiveNGDB(model, kg, eng, finetune_steps=2) as live:
            r = live.write(np.array([[n0, 0, 1], [n0 + 1, 1, n0]]),
                           n_new_entities=2)
            assert r.n_new_entities == 2 and r.n_written == 2
            assert kg.n_entities == model.n_entities == n0 + 2
            live.flush()
            # the new ids are servable immediately
            q = QueryInstance("1p", np.array([n0]), np.array([0]))
            assert eng.submit(q).result(timeout=30)["anchors"] == [n0]


def test_background_finetune_matches_sync_rerun():
    """The maintenance thread's fine-tune is a pure function of
    (params, triples, seed): a synchronous rerun from the recorded inputs
    reproduces the served params bitwise."""
    kg, model, params, ex = _fresh_setup()
    cfg = ServingConfig(max_batch=8, max_wait_ms=2.0,
                        max_staleness_versions=8)
    burst = _fresh_rows(kg, 3)
    with ServingEngine(model, params, executor=ex, cfg=cfg, kg=kg) as eng:
        with LiveNGDB(model, kg, eng, finetune_steps=3, seed=11) as live:
            r = live.write(burst)
            assert r.n_written == 3
            live.flush()
            served = eng.params
        sync, losses = incremental_finetune(
            model, params, r.fresh_triples, steps=3, lr=live.finetune_lr,
            n_negatives=live.n_negatives, seed=11 + r.graph_version)
    assert set(served) == set(sync)
    for k in served:
        np.testing.assert_array_equal(np.asarray(served[k]),
                                      np.asarray(sync[k]))
    assert len(losses) == 3 and all(np.isfinite(losses))


def test_maintained_params_score_written_triples_near_a_rebuild():
    """After several write bursts, the incrementally maintained params'
    loss on everything written stays within 2.0 of a from-scratch
    fine-tune of the pre-write params on the same triples."""
    kg, model, params, ex = _fresh_setup()
    cfg = ServingConfig(max_batch=8, max_wait_ms=2.0,
                        max_staleness_versions=8)
    with ServingEngine(model, params, executor=ex, cfg=cfg, kg=kg) as eng:
        with LiveNGDB(model, kg, eng, finetune_steps=2, seed=0) as live:
            for k in range(3):
                live.write(_fresh_rows(kg, 4, seed=k))
            live.flush()
            maintained = eng.params
            touched = np.concatenate([r.fresh_triples for r in live.receipts
                                      if r.n_written])
            steps = live.finetune_steps * live.finetunes_done
            lr = live.finetune_lr
    rebuilt, _ = incremental_finetune(model, params, touched, lr=lr,
                                      steps=steps, seed=1)
    _, inc = incremental_finetune(model, maintained, touched, steps=1, seed=1)
    _, reb = incremental_finetune(model, rebuilt, touched, steps=1, seed=1)
    assert inc[0] <= reb[0] + 2.0, (inc, reb)


def test_incremental_finetune_deterministic_and_learns(tiny_kg):
    model = make_model("gqe", ModelConfig(dim=16, gamma=6.0))
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    burst = tiny_kg.triples[:12]
    a, la = incremental_finetune(model, params, burst, steps=8, lr=1e-2,
                                 seed=4)
    b, lb = incremental_finetune(model, params, burst, steps=8, lr=1e-2,
                                 seed=4)
    assert la == lb
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert la[-1] < la[0]   # the touched neighborhood actually improves


def test_engine_rejects_kg_with_sem_cache(tiny_kg):
    """Device hot-set staging mutates params in place per batch — that is
    incompatible with version-pinned replay, so the combination is refused
    up front."""
    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    kg = KnowledgeGraph(4, 2, np.array([[0, 0, 1]]))
    with pytest.raises(ValueError, match="sem_cache"):
        ServingEngine(model, params, executor=PooledExecutor(model, b_max=64),
                      cfg=ServingConfig(), kg=kg, sem_cache=object(),
                      sem_rows_fn=lambda ids: ids)
