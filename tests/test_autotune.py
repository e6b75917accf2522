"""Autotuner subsystem tests (DESIGN.md §Autotuner).

Covers: the kernel-aware ``bucket_size``/``row_block`` shape math, tuned
configs bitwise-equal to the reference-default path across (pool-rows, dim)
buckets, persisted-cache round-trip + corrupt/partial-file rejection, the
``PoolTilePolicy`` bridge (bitwise encodes + closed signature universe), and
the ValueError shape contracts that replaced bare asserts.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scheduler import bucket_size
from repro.kernels import autotune as at
from repro.kernels import ops
from repro.kernels.ref import gather_fuse_ref, intersect_ref, scoring_ref


@pytest.fixture
def tuner(tmp_path):
    return at.KernelTuner(path=str(tmp_path / "tiles.json"), iters=1,
                          warmup=0)


@pytest.fixture(autouse=True)
def _isolate_global_tuner():
    prev = at.set_tuner(None)
    yield
    at.set_tuner(prev)


# ------------------------------------------------------------- shape math
def test_pow2ceil_and_ceil_to():
    assert [at.pow2ceil(n) for n in (0, 1, 2, 3, 8, 9)] == [1, 1, 2, 4, 8, 16]
    assert at.ceil_to(13, 8) == 16
    assert at.ceil_to(16, 8) == 16


@pytest.mark.parametrize("n", [1, 3, 8, 13, 100, 288, 511])
@pytest.mark.parametrize("tile", [1, 8, 32, 128, 256])
def test_row_block_properties(n, tile):
    block, padded = at.row_block(n, tile)
    assert padded >= n
    assert padded % block == 0
    assert block <= max(8, at.pow2ceil(n))
    # Never worse than bare pow2 padding.
    assert padded <= max(8, at.pow2ceil(n))


@pytest.mark.parametrize("n", [1, 5, 17, 100, 288, 500, 512, 700])
@pytest.mark.parametrize("b_max", [128, 512])
@pytest.mark.parametrize("tile", [1, 8, 64, 256])
def test_bucket_size_kernel_aware(n, b_max, tile):
    pow2 = bucket_size(n, b_max)
    tiled = bucket_size(n, b_max, tile)
    assert tiled <= pow2                       # never MORE pad than pow2
    assert tiled >= min(n, b_max)              # still covers the pool
    if tile > 1 and n < b_max:
        assert tiled % min(tile, pow2) == 0    # launch-aligned
    assert bucket_size(n, b_max, 1) == pow2    # tile=1 is the legacy rule


def test_bucket_size_saves_pad_waste():
    # The motivating case: 288 rows with a 128-row tile pads to 384, not 512.
    assert bucket_size(288, 512) == 512
    assert bucket_size(288, 512, 128) == 384


# -------------------------------------------- tuned configs are bitwise
@pytest.mark.parametrize("bucket", [(8, 128, 32), (32, 256, 64)])
def test_scoring_tuned_bitwise(tuner, bucket, rng):
    cfg = tuner.tune("scoring", bucket)
    B, N, d = 7, 100, bucket[2]
    q = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    tuned = ops.scoring(q, e, gamma=1.5, mode="dot", bm=cfg["bm"],
                        bn=cfg["bn"], bk=cfg["bk"], interpret=True)
    default = ops.scoring(q, e, gamma=1.5, mode="dot", interpret=True)
    assert np.array_equal(np.asarray(tuned), np.asarray(default))
    np.testing.assert_allclose(
        np.asarray(tuned), np.asarray(scoring_ref(q, e, 1.5, "dot")),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bucket", [(16, 2, 32, 64), (64, 3, 16, 32)])
def test_intersect_tuned_bitwise(tuner, bucket, rng):
    cfg = tuner.tune("intersect", bucket)
    n, k, d, hd = 13, bucket[1], bucket[2], bucket[3]
    x = jnp.asarray(rng.normal(size=(n, k, d)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(d, hd)) * 0.2, jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(hd,)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(hd, 1)) * 0.2, jnp.float32)
    b2 = jnp.zeros((1,), jnp.float32)
    tuned = ops.intersect(x, w1, b1, w2, b2, bn=cfg["bn"], interpret=True)
    default = ops.intersect(x, w1, b1, w2, b2, interpret=True)
    assert np.array_equal(np.asarray(tuned), np.asarray(default))
    np.testing.assert_allclose(
        np.asarray(tuned), np.asarray(intersect_ref(x, w1, b1, w2, b2)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows", [1, 4, 16])
def test_gather_fuse_rows_bitwise(rows, rng):
    """Every blocked launch geometry produces the SAME bits as the rows=1
    scalar-prefetch path — blocking only moves work."""
    E, d, dl, dp, n = 60, 16, 8, 4, 21
    ids = jnp.asarray(rng.integers(0, E, n), jnp.int32)
    h_str = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
    h_sem = jnp.asarray(rng.normal(size=(E, dl)), jnp.float32)
    wp = jnp.asarray(rng.normal(size=(dl, dp)) * 0.2, jnp.float32)
    bp = jnp.asarray(rng.normal(size=(dp,)) * 0.1, jnp.float32)
    wf = jnp.asarray(rng.normal(size=(d + dp, d)) * 0.2, jnp.float32)
    bf = jnp.zeros((d,), jnp.float32)
    base = ops.gather_fuse(ids, h_str, h_sem, wp, bp, wf, bf, rows=1,
                           interpret=True)
    out = ops.gather_fuse(ids, h_str, h_sem, wp, bp, wf, bf, rows=rows,
                          interpret=True)
    assert np.array_equal(np.asarray(base), np.asarray(out))
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(gather_fuse_ref(ids, h_str, h_sem, wp, bp, wf, bf)),
        rtol=1e-5, atol=1e-5)


def test_sweep_rejects_nonbitwise_candidates(tuner, monkeypatch):
    """A candidate whose output differs by one bit must be rejected, not
    timed into the cache."""
    real = at._make_runner

    def poisoned(op, bucket, dtype, interpret):
        run, args = real(op, bucket, dtype, interpret)

        def bad_run(cfg, *a):
            out = run(cfg, *a)
            if cfg.get("bn") == 8:  # poison one non-default candidate
                return jnp.asarray(np.asarray(out) + 1e-7)
            return out

        return bad_run, args

    monkeypatch.setattr(at, "_make_runner", poisoned)
    cfg = tuner.tune("intersect", (16, 2, 16, 32))
    assert cfg["bn"] != 8
    assert int(tuner.verify_rejects) >= 1


# ------------------------------------------------------ persisted cache
def test_cache_roundtrip(tuner, tmp_path):
    cfg = tuner.tune("intersect", (16, 2, 16, 32))
    assert os.path.exists(tuner.path)
    fresh = at.KernelTuner(path=tuner.path, iters=1, warmup=0)
    assert len(fresh) == 1
    assert fresh.tune("intersect", (16, 2, 16, 32)) == cfg
    assert int(fresh.sweeps) == 0  # served from disk, no re-sweep


@pytest.mark.parametrize("payload", [
    "not json at all {{{",
    '{"version": 99, "entries": {}}',
    '{"version": 1}',
    '{"version": 1, "entries": {"k": {"op": "intersect"}}}',
    '{"version": 1, "entries": {"k": {"op": "nope", "config": {"bn": 8}}}}',
    '{"version": 1, "entries": {"k": {"op": "intersect", '
    '"config": {"bn": -4}}}}',
    '{"version": 1, "entries": {"k": {"op": "intersect", '
    '"config": {"wrong_key": 8}}}}',
])
def test_corrupt_cache_rejected_not_crashed(tmp_path, payload):
    p = tmp_path / "tiles.json"
    p.write_text(payload)
    t = at.KernelTuner(path=str(p), iters=1, warmup=0)
    assert len(t) == 0                 # nothing partial leaked in
    assert t.load_error is not None    # and the rejection is recorded
    assert int(t.load_rejects) == 1
    # ...and the tuner still tunes (retune instead of crash).
    cfg = t.tune("intersect", (16, 2, 16, 32))
    assert set(cfg) == {"bn"}
    # The rewrite repaired the file.
    fresh = at.KernelTuner(path=str(p))
    assert fresh.load_error is None and len(fresh) == 1


def test_partial_write_never_visible(tuner):
    """Crash-safe publish: the cache file is always complete JSON (tmp +
    rename), so a reader can never observe partial bytes."""
    tuner.tune("intersect", (16, 2, 16, 32))
    with open(tuner.path) as f:
        payload = json.load(f)
    assert payload["version"] == at.CACHE_VERSION
    assert not os.path.exists(tuner.path + ".tmp")


def test_env_var_names_default_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(at.ENV_CACHE, str(tmp_path / "env_tiles.json"))
    at.set_tuner(None)
    t = at.get_tuner()
    assert t.path == str(tmp_path / "env_tiles.json")


# ---------------------------------------------------- PoolTilePolicy
def _tuned_policy_for(model, tuner, b_max=64):
    n = at.tune_for_model(model, tuner, b_max=b_max, batch=16)
    assert n > 0
    policy = at.pool_tile_policy(model, tuner, b_max=b_max)
    assert policy  # entries matched the model dims
    return policy


def test_pool_tile_policy_bitwise_and_closed(tiny_kg, tuner, rng):
    import jax

    from repro.core import PooledExecutor
    from repro.models import ModelConfig, make_model
    from repro.sampling import OnlineSampler

    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    policy = _tuned_policy_for(model, tuner)
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations)
    qs = [s.query for s in OnlineSampler(tiny_kg, seed=3).sample_batch(24)]
    tuned_ex = PooledExecutor(model, b_max=64, tile_policy=policy)
    plain_ex = PooledExecutor(model, b_max=64, tile_policy=None)
    enc_t = np.asarray(tuned_ex.encode(params, qs))
    enc_p = np.asarray(plain_ex.encode(params, qs))
    assert np.array_equal(enc_t, enc_p)  # padding must not move real rows

    # Closed signature universe: replaying the same queries compiles nothing.
    tuned_ex.reset_cache_counters()
    np.asarray(tuned_ex.encode(params, qs))
    stats = tuned_ex.cache_stats()
    assert all(int(stats[k]["misses"]) == 0
               for k in ("schedule", "encode", "encode_jit")), stats


def test_policy_key_separates_cache_entries(tiny_kg, tuner):
    """Two executors with different tunings must not alias schedules: the
    policy key is part of every schedule/plan cache key."""
    from repro.core.compiler import compile_batch
    from repro.models import ModelConfig, make_model
    from repro.sampling import OnlineSampler

    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    policy = _tuned_policy_for(model, tuner)
    qs = [s.query for s in OnlineSampler(tiny_kg, seed=3).sample_batch(24)]
    plain = compile_batch(qs, model_name=model.name, b_max=64)
    tuned = compile_batch(qs, model_name=model.name, b_max=64,
                          tile_policy=policy)
    assert plain.structure_key != tuned.structure_key


def test_untuned_tuner_means_no_policy():
    from repro.models import ModelConfig, make_model

    model = make_model("gqe", ModelConfig(dim=8))
    t = at.KernelTuner()  # no entries
    assert at.pool_tile_policy(model, t) is None


def test_executor_auto_snapshots_process_tuner(tiny_kg, tuner):
    from repro.core import PooledExecutor
    from repro.models import ModelConfig, make_model

    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    _tuned_policy_for(model, tuner)
    at.set_tuner(tuner)
    ex = PooledExecutor(model, b_max=64)  # tile_policy="auto"
    assert ex.tile_policy
    at.set_tuner(None)
    ex2 = PooledExecutor(model, b_max=64)
    assert ex2.tile_policy is None


@pytest.mark.parametrize("pipeline", [False, True])
def test_trainer_replay_compiles_nothing_under_tile_policy(tiny_kg, tuner,
                                                           pipeline):
    """With the process tuner set, the trainer's executor snapshots a live
    tile policy, and replaying a warm batch list compiles nothing."""
    from repro.models import ModelConfig, make_model
    from repro.sampling import OnlineSampler
    from repro.training import AdamConfig, NGDBTrainer, TrainConfig

    model = make_model("gqe", ModelConfig(dim=8, gamma=6.0))
    _tuned_policy_for(model, tuner)
    at.set_tuner(tuner)
    cfg = TrainConfig(batch_size=16, n_negatives=4, b_max=64, seed=0,
                      adam=AdamConfig(lr=1e-3), pipeline=pipeline)
    tr = NGDBTrainer(model, tiny_kg, cfg)
    assert tr.executor.tile_policy
    batches = [OnlineSampler(tiny_kg, seed=29).sample_batch(16)
               for _ in range(2)]
    tr.train(2, log_every=0, batches=batches)
    tr._train_fns.reset_counters()
    tr.executor.reset_cache_counters()
    tr.train(4, log_every=0, batches=batches)
    cs = tr.compile_cache_stats()
    assert all(int(cs[k]["misses"]) == 0 for k in
               ("train_step", "schedule", "encode", "encode_jit")), cs


# ------------------------------------------------- ValueError contracts
def test_scoring_shape_errors():
    from repro.kernels.scoring import scoring_pallas

    q = jnp.zeros((10, 128), jnp.float32)
    e = jnp.zeros((256, 128), jnp.float32)
    with pytest.raises(ValueError, match="B=10.*bm=128"):
        scoring_pallas(q, e, bm=128, bn=256, bk=128, interpret=True)
    with pytest.raises(ValueError, match="N=100.*bn=256"):
        scoring_pallas(jnp.zeros((128, 128)), jnp.zeros((100, 128)),
                       bm=128, bn=256, bk=128, interpret=True)
    with pytest.raises(ValueError, match="d=64.*bk=128"):
        scoring_pallas(jnp.zeros((128, 64)), jnp.zeros((256, 64)),
                       bm=128, bn=256, bk=128, interpret=True)
    with pytest.raises(ValueError, match="d=128 != e feature dim d=64"):
        scoring_pallas(jnp.zeros((128, 128)), jnp.zeros((256, 64)),
                       bm=128, bn=256, bk=64, interpret=True)


def test_intersect_shape_errors():
    from repro.kernels.intersect import intersect_pallas

    x = jnp.zeros((10, 2, 32), jnp.float32)
    w1 = jnp.zeros((32, 64), jnp.float32)
    b1 = jnp.zeros((64,), jnp.float32)
    w2 = jnp.zeros((64, 128), jnp.float32)
    b2 = jnp.zeros((128,), jnp.float32)
    with pytest.raises(ValueError, match="n=10.*bn=256"):
        intersect_pallas(x, w1, b1, w2, b2, bn=256, interpret=True)
    with pytest.raises(ValueError, match="input dim 16 != state"):
        intersect_pallas(jnp.zeros((8, 2, 32)), jnp.zeros((16, 64)), b1,
                         w2, b2, bn=8, interpret=True)


def test_gather_fuse_shape_errors():
    from repro.kernels.gather_fuse import gather_fuse_pallas

    ids = jnp.zeros((10,), jnp.int32)
    h_str = jnp.zeros((16, 8), jnp.float32)
    h_sem = jnp.zeros((16, 4), jnp.float32)
    wp = jnp.zeros((4, 4), jnp.float32)
    bp = jnp.zeros((4,), jnp.float32)
    wf = jnp.zeros((12, 8), jnp.float32)
    bf = jnp.zeros((8,), jnp.float32)
    with pytest.raises(ValueError, match="n=10.*rows=4"):
        gather_fuse_pallas(ids, h_str, h_sem, wp, bp, wf, bf, rows=4,
                           interpret=True)
    with pytest.raises(ValueError, match="rows must be >= 1"):
        gather_fuse_pallas(ids, h_str, h_sem, wp, bp, wf, bf, rows=0,
                           interpret=True)
    with pytest.raises(ValueError, match="sem_ids shape"):
        gather_fuse_pallas(ids, h_str, h_sem, wp, bp, wf, bf,
                           jnp.zeros((4,), jnp.int32), rows=1,
                           interpret=True)


# ------------------------------------------------------------- metrics
def test_autotune_metrics_published(tuner):
    from repro.obs import get_registry

    tuner.tune("intersect", (16, 2, 16, 32))
    tuner.config_for("intersect", (16, 2, 16, 32))
    tuner.config_for("intersect", (999, 2, 16, 32))  # untuned -> default
    snap = get_registry().snapshot()
    assert snap["autotune_sweeps"] >= 1
    assert snap["autotune_lookup_hits"] >= 1
    assert snap["autotune_lookup_misses"] >= 1
    assert snap["autotune_entries"] == 1
