"""Training cells over a mesh of chips: the pipelined ``NGDBTrainer.train``
under the configuration's ``trainer.mesh`` and ``trainer.profile``.

The window, the hooks into the trainer and the result are the single-chip
runner's (``kinds/train.py``), run from a private instance of that module
whose three seams are bound to the mesh:

* weights: the benchmark's own init (``reference.init_params``), jitted with
  ``out_shardings`` equal to the trainer's parameter shardings, so no device
  ever holds a whole table; the values are the same bits;
* trainer: the program's ``NGDBTrainer`` with the ``ExecutionContext`` of
  the mesh over the run's chips;
* reference: ``reference_rows``, the plain reference on the entity rows the
  checked steps touch, on the first chip.

The deployment's layout is part of ``correct``: ``table_collective`` is the
largest collective of the compiled train steps, as the program reads it from
each step's partitioned module, over the bytes one chip holds of the entity
table. A step that gathered the table, or its gradient, whole reads 1 or
more. A program that cannot report its step's collectives is refused before
the graph is built.
"""
from __future__ import annotations

import functools
import gc
import types
from typing import Dict

from bench import checks, harness, reference, reference_rows


def sharded_init(ctx, cfg: Dict, seed: int):
    """``reference.init_params`` with every leaf made in place in its
    parameter sharding."""
    import jax

    def init():
        return reference.init_params(cfg, seed)

    shardings = ctx.param_shardings(jax.eval_shape(init))
    return jax.jit(init, out_shardings=shardings)()


def build_trainer(run, params, ctx):
    """The program's trainer for the configuration, on ``params``, over
    ``ctx``."""
    import jax
    from repro.models import ModelConfig, make_model
    from repro.training import AdamConfig, NGDBTrainer, TrainConfig

    cfg, t = run.cfg, run.cfg["trainer"]
    g = cfg["graph"]
    model = make_model(cfg["family"], ModelConfig(**cfg["model"]))
    want = jax.eval_shape(
        lambda k: type(model).init_params(model, k, g["n_entities"],
                                          g["n_relations"]),
        jax.random.PRNGKey(0))
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in params.items()}
    exp = {k: (tuple(v.shape), str(v.dtype)) for k, v in want.items()}
    if got != exp:
        raise ValueError(f"benchmark params {got} != program's {exp}")
    if ctx.is_sharded and ctx.param_shardings(params) != {
            k: v.sharding for k, v in params.items()}:
        raise ValueError("benchmark params are not in the trainer's "
                         "parameter shardings")

    def supplied(key, n_entities, n_relations, **kw):
        model.n_entities = n_entities
        return params

    model.init_params = supplied
    tcfg = TrainConfig(
        batch_size=t["batch_size"], n_negatives=t["n_negatives"],
        b_max=t["b_max"], adam=AdamConfig(**t["adam"]), pipeline=True,
        max_inflight=t["max_inflight"], prefetch=t["prefetch"], cse=t["cse"],
        patterns=tuple(run.mix["patterns"]),
        seed=harness.derive_seed(run.args.seed, "negatives"))
    return NGDBTrainer(model, run.kg, tcfg, ctx=ctx)


def table_collective(trainer) -> float:
    """The largest collective of the trainer's compiled steps over one
    chip's bytes of the entity table."""
    ent = trainer.params["entity"]
    shard = max(s.data.nbytes for s in ent.addressable_shards)
    stats = trainer.step_collectives.values()
    if not stats:
        raise RuntimeError("the trainer read no compiled step's collectives")
    return max(st.largest_bytes for st in stats) / shard


def run(run) -> Dict:
    from repro.distributed.context import make_execution_context
    from repro.training import NGDBTrainer

    if not hasattr(NGDBTrainer, "step_collectives"):
        raise RuntimeError("this program does not report its compiled train "
                           "step's collectives, which this cell's "
                           "table_collective check reads")
    t = run.cfg["trainer"]
    ctx = make_execution_context(t["mesh"], profile=t["profile"],
                                 devices=run.devices)
    harness.log(f"bench: {ctx.describe()} over {ctx.n_devices} chips")
    init = functools.partial(sharded_init, ctx)
    held = {}

    def build(run, params):
        held["trainer"] = build_trainer(run, params, ctx)
        return held["trainer"]

    def train_readings(cell, cfg, seed, inputs, prog):
        tr = held.pop("trainer")
        layout = table_collective(tr)
        del tr
        gc.collect()
        ref = reference_rows.reference_readings(cfg, seed, inputs, init,
                                                device=run.devices[0])
        harness.log(f"bench: reference on {ref['rows']} entity rows, "
                    f"losses {ref['losses']}, program {prog['losses']}")
        values = checks.compare_train(prog, ref)
        values["table_collective"] = layout
        return checks._with_limits(cell["name"], values)

    base = harness.load_module("kinds", "train")
    base.reference = types.SimpleNamespace(init_params=init)
    base.build_trainer = build
    base.checks = types.SimpleNamespace(judge=checks.judge,
                                        train_readings=train_readings)
    return base.run(run)
