"""The comparison that decides ``correct``.

Each number is compared with its limit from ``bench/limits/<workload>.json``
(set from the readings ``PERF.md`` lists: the program's sound runs below,
the control and the planted faults above).

Training: the reference follows the checked steps of the timed call from the
same weights and inputs, and three numbers are read:

* ``loss_rel``: the largest |program loss - reference loss| / |reference
  loss| over the checked steps;
* ``grad_gap``: the first gradient as Adam got it (its first moment over
  ``1 - b1``), by the worst leaf: |program norm - reference norm| over the
  larger of that leaf's reference norm and the median leaf's;
* ``change_gap``: the same for the change of the parameters over the
  checked steps.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's (a bias under a softmax) move by round-off alone and are left out.

"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence

import numpy as np

from bench import harness, reference

NEGLIGIBLE = 1e-3


def limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(harness.BENCH, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def judge(cell: Dict, readings: Dict[str, Dict]) -> bool:
    return all(np.isfinite(r["value"]) and r["value"] <= r["limit"]
               for r in readings.values())


def _with_limits(cell_name: str, values: Dict[str, float]) -> Dict:
    """The numbers the cell's limits file names, each beside its limit. A
    number it does not name has no upper reading (``PERF.md`` gives its
    readings): it is logged and not compared."""
    lim = limits(cell_name)
    for k in sorted(set(values) - set(lim)):
        harness.log(f"bench: {k} {values[k]!r} (not compared)")
    return {k: {"value": float(v), "limit": float(lim[k])}
            for k, v in values.items() if k in lim}


# ------------------------------------------------------------------ training
def as_queries(queries) -> List[reference.Query]:
    return [(q.pattern, np.asarray(q.anchors), np.asarray(q.relations))
            for q in queries]


def _norms(tree) -> Dict[str, float]:
    import jax.numpy as jnp

    return {k: float(jnp.linalg.norm(v.astype(jnp.float32).ravel()))
            for k, v in tree.items()}


def reference_readings(cfg: Dict, seed: int, inputs: Sequence,
                       precision: str = "highest", keep: float = 1.0
                       ) -> Dict:
    """Losses, first-gradient leaf norms and the change's leaf norms of the
    reference over ``inputs`` ((queries, pos, neg) per step)."""
    import jax

    hp = cfg["trainer"]["adam"]
    p0 = reference.init_params(cfg, seed)
    p, state, losses, grad1 = p0, None, [], None
    for queries, pos, neg in inputs:
        loss, g = reference.loss_and_grads(cfg, p, as_queries(queries), pos,
                                           neg, precision=precision,
                                           keep=keep)
        if grad1 is None:
            grad1 = _norms(g)
        p, state = reference.adam(p, g, state, hp)
        losses.append(loss)
    change = _norms(jax.tree.map(
        lambda a, b: a.astype(np.float32) - b, p, p0))
    return {"losses": losses, "grad1": grad1, "change": change}


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: List[str]) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def compare_train(prog: Dict, ref: Dict) -> Dict[str, float]:
    med = float(np.median(list(ref["grad1"].values())))
    keep = sorted(k for k, v in ref["grad1"].items()
                  if v >= NEGLIGIBLE * med)
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))
    return {"loss_rel": loss,
            "grad_gap": _leaf_gap(prog["grad1"], ref["grad1"], keep),
            "change_gap": _leaf_gap(prog["change"], ref["change"], keep)}


def train_readings(cell: Dict, cfg: Dict, seed: int, inputs: Sequence,
                   prog: Dict) -> Dict:
    ref = reference_readings(cfg, seed, inputs)
    values = compare_train(prog, ref)
    harness.log(f"bench: reference losses {ref['losses']}, program "
                f"{prog['losses']}")
    return _with_limits(cell["name"], values)
