"""Pipelined training over a 4-device mesh (fsdp profile, ``data=4``) at a
small size, on emulated CPU devices in a subprocess (``XLA_FLAGS`` must be
set before JAX starts).

One run trains the sharded trainer and the one-device trainer on the same
three batches from the same weights (the benchmark's mesh cell, shrunk by
``bench/tests/tiny_mesh.py``, on six templates that cover every operator)
and reads:

* the first three losses, the first gradient as Adam got it and the change
  after three steps, against the plain reference on the touched rows
  (``bench/reference_rows.py``) within the cell's limits, and against the
  one-device trainer;
* the compiled sharded step's collectives: none carries as much as one
  device's shard of the entity table;
* the main lane's ``dispatch`` spans: the sharded step's carry
  ``collective_wire_bytes`` > 0, the one-device step's carry none, and
  reading the module compiled the step once per signature.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench", "tests"))

import tiny_mesh  # noqa: E402

PATTERNS = ["1p", "3p", "2i", "up", "2in", "pni"]

BODY = r"""
import json
import jax
import jax.numpy as jnp
from types import SimpleNamespace
from bench import checks, harness, reference, reference_rows
from bench.kinds import train_mesh
from repro.distributed.context import ExecutionContext, make_execution_context
from repro.obs.registry import get_registry
from repro.obs.trace import TRACER

compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(kw.get("fun_name"))
    if event == "/jax/core/compile/backend_compile_duration" else None)
cell = harness.find_cell(harness.load_benchmark(), tiny_mesh.CELL)
cfg = harness.load_config(cell["config"])
mix = dict(harness.load_traffic(cell["traffic"]), patterns=PATTERNS)
seed = 4294967311
kg = harness.build_graph(cfg)
t = cfg["trainer"]
feed = harness.train_feed(harness.online_sampler(kg, mix, 7), mix,
                          t["batch_size"], 7)
batches = [feed() for _ in range(3)]
mesh = make_execution_context(t["mesh"], profile=t["profile"])
b1 = t["adam"]["b1"]


def norms(tree):
    return {k: float(jnp.linalg.norm(v.ravel())) for k, v in tree.items()}


def train(ctx, params):
    run = SimpleNamespace(cfg=cfg, mix=mix, kg=kg,
                          args=SimpleNamespace(seed=seed))
    before = get_registry().snapshot()
    tr = train_mesh.build_trainer(run, params, ctx)
    gauge = get_registry().delta(before)["trainer_entity_bytes_per_device"]
    inputs, m1 = [], []
    arrays = tr.sampler.to_training_arrays

    def recorded(batch, k):
        out = arrays(batch, k)
        if len(inputs) < 3:
            inputs.append(out)
        return out

    tr.sampler.to_training_arrays = recorded
    train_fn = tr._train_fn

    def hooked(prepared, example=None):
        fn = train_fn(prepared, example)

        def step(*a):
            out = fn(*a)
            if not m1:
                m1.append(norms(out[1]["m"]))
            return out
        return step

    tr._train_fn = hooked
    TRACER.enable(jax_annotations=False)
    n0 = len(compiles)
    tr.train(3, log_every=0, batches=batches)
    TRACER.disable()
    step_compiles = compiles[n0:].count("jit(step_fn)")
    p0 = params_for(ctx)
    wire = [ev.get("args", {}).get("collective_wire_bytes")
            for ev in TRACER.events() if ev.get("name") in ("compile",
                                                            "dispatch")]
    ent = tr.params["entity"]
    return tr, inputs, {
        "losses": [r["loss"] for r in tr.history],
        "grad1": {k: v / (1 - b1) for k, v in m1[0].items()},
        "change": norms(jax.tree.map(lambda a, b: a - b, tr.params, p0)),
        "wire": wire,
        "shard_bytes": max(s.data.nbytes for s in ent.addressable_shards),
        "table_bytes": ent.nbytes, "gauge": gauge,
        "step_compiles": step_compiles}


def params_for(ctx):
    if ctx.is_sharded:
        return train_mesh.sharded_init(ctx, cfg, seed)
    return reference.init_params(cfg, seed)


sharded, inputs, prog = train(mesh, params_for(mesh))
colls = list(sharded.step_collectives.values())
_, _, single = train(ExecutionContext.single_device(),
                     params_for(ExecutionContext.single_device()))
init = lambda c, s: train_mesh.sharded_init(mesh, c, s)
ref = reference_rows.reference_readings(cfg, seed, inputs, init)
print(json.dumps({
    "vs_reference": checks.compare_train(prog, ref),
    "vs_single": checks.compare_train(prog, single),
    "limits": checks.limits(tiny_mesh.CELL),
    "largest": max(st.largest_bytes for st in colls),
    "wire_bytes": [st.wire_bytes for st in colls],
    "shard_bytes": prog["shard_bytes"], "table_bytes": prog["table_bytes"],
    "gauges": [prog["gauge"], single["gauge"]],
    "sharded_wire": prog["wire"], "single_wire": single["wire"],
    "n_signatures": len(colls), "step_compiles": prog["step_compiles"]}))
"""


@pytest.fixture(scope="module")
def out():
    return tiny_mesh.run(f"PATTERNS = {PATTERNS!r}\n" + BODY)


def test_sharded_matches_reference_within_the_cell_limits(out):
    lim = out["limits"]
    for k, v in out["vs_reference"].items():
        if k in lim:
            assert v <= lim[k], (k, v, lim[k])


def test_sharded_matches_one_device(out):
    gaps = out["vs_single"]
    assert gaps["loss_rel"] < 1e-5
    assert gaps["grad_gap"] < 1e-4
    assert gaps["change_gap"] < 0.02     # att_b0: a hidden unit on the edge


def test_no_collective_carries_an_entity_shard(out):
    assert out["shard_bytes"] * 4 == out["table_bytes"]
    assert out["gauges"] == [out["shard_bytes"], out["table_bytes"]]
    assert out["n_signatures"] >= 1 and min(out["wire_bytes"]) > 0
    assert 0 < out["largest"] < out["shard_bytes"]


def test_dispatch_spans_carry_wire_bytes_only_under_a_mesh(out):
    # reading the compiled module costs no second compile of the step
    assert out["step_compiles"] == out["n_signatures"]
    assert out["sharded_wire"] and all(w and w > 0
                                       for w in out["sharded_wire"])
    assert out["single_wire"] and all(w is None for w in out["single_wire"])
