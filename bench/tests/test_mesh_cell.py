"""The mesh cell at a small size on 4 emulated CPU devices: the row-restricted
reference against the dense one, the sharded weights against the
benchmark's init, and whole runs past the look for a chip: sound, with half
of each batch left out of the loss, and with the exchange between chips
left out of the lookup."""
import pytest

import tiny_mesh

EXACT = r"""
import json
import jax
import numpy as np
from bench import checks, harness, reference, reference_rows
from bench.kinds import train_mesh
from bench.tools import control
from repro.distributed.context import make_execution_context

cell = harness.find_cell(harness.load_benchmark(), tiny_mesh.CELL)
cfg = harness.load_config(cell["config"])
mix = harness.load_traffic(cell["traffic"])
kg = harness.build_graph(cfg)
seed = 4294967311
ctx = make_execution_context(cfg["trainer"]["mesh"],
                             profile=cfg["trainer"]["profile"])
init = lambda c, s: train_mesh.sharded_init(ctx, c, s)
sharded, dense = init(cfg, seed), reference.init_params(cfg, seed)
bitwise = all(np.array_equal(np.asarray(sharded[k]), np.asarray(dense[k]))
              for k in dense)
ent = sharded["entity"]
split = max(s.data.nbytes for s in ent.addressable_shards) * 4 == ent.nbytes
inputs = control.train_inputs(cfg, mix, kg, seed, mix["checked_steps"])
rows = reference_rows.reference_readings(cfg, seed, inputs, init)
full = checks.reference_readings(cfg, seed, inputs)
rel = lambda a, b: abs(a - b) / max(abs(b), 1e-30)
gap = max([rel(a, b) for a, b in zip(rows["losses"], full["losses"])]
          + [rel(rows[w][k], full[w][k]) for w in ("grad1", "change")
             for k in full[w]])
print(json.dumps({"bitwise": bitwise, "split": split, "gap": gap,
                  "rows": rows["rows"], "n_entities": kg.n_entities}))
"""

RUN = r"""
from bench import run
{fault}
rc = run.main(["--workload", tiny_mesh.CELL, "--seed", "2718281828",
               "--seconds", "2", "--trace", "0"], require_tpu=False)
assert rc == 0, rc
"""

HALF_BATCH = r"""
from repro.training import loop
real = loop.negative_sampling_loss
def half(model, params, q, pos, neg):
    n = q.shape[0] // 2
    return real(model, params, q[:n], pos[:n], neg[:n])
loop.negative_sampling_loss = half
"""

# The exchange between chips left out: each chip reads only the rows of the
# entity table it holds, rows held elsewhere read 0 (the lookup's all-reduce
# dropped).
NO_EXCHANGE = r"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.models import base
mesh = Mesh(np.asarray(jax.devices()).reshape(4, 1), ("data", "model"))
def local_rows(table, ids):
    n = table.shape[0]
    lo = jax.lax.axis_index("data") * n
    hit = (ids >= lo) & (ids < lo + n)
    rows = table[jnp.where(hit, ids - lo, 0)]
    return jnp.where(hit[..., None], rows, 0.0)
def no_exchange(self, params, ent_ids):
    return jax.shard_map(local_rows, mesh=mesh,
                         in_specs=(P("data", None), P()), out_specs=P(),
                         check_vma=False)(params["entity"], ent_ids)
base.QueryEncoder.fused_entity_vec = no_exchange
"""


def test_row_reference_is_the_dense_reference_and_init_is_bitwise():
    out = tiny_mesh.run(EXACT)
    assert out["bitwise"] and out["split"]
    assert 0 < out["rows"] < out["n_entities"]
    assert out["gap"] <= 1e-6


@pytest.mark.parametrize("fault", ["", HALF_BATCH, NO_EXCHANGE],
                         ids=["sound", "half", "no_exchange"])
def test_whole_run(fault):
    out = tiny_mesh.run(RUN.format(fault=fault))
    assert out["device"]["count"] == 4
    assert out["correct"] is (not fault)
    checks = out["checks"]
    if fault is NO_EXCHANGE:
        # caught by the numbers, whatever the layout check reads
        assert checks["loss_rel"]["value"] > checks["loss_rel"]["limit"]
    else:
        assert 0 < checks["table_collective"]["value"] < 0.5
