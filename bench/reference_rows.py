"""The plain reference on the entity rows the checked steps touch.

A table too large for one chip (ogbl-wikikg2: 2,500,604 x 400 float32, 16 GB
with its gradient and Adam's m and v) is followed on the union of the rows
that the checked steps read: every anchor, positive and negative. Those rows
are taken from the cell's weights and renumbered into a compact table; every
other leaf is taken whole. The steps then run through ``reference.
loss_and_grads`` and ``reference.adam`` unchanged, on one device.

The restriction is exact, not an approximation. With no weight decay, a row
whose gradient is zero at every checked step keeps Adam's m = v = 0, so its
update is lr * 0 / (0 + eps) = 0: it adds nothing to the gradient's or the
change's leaf norm, and the compact table's norms are the whole table's. The
same holds for the untouched rows ``padded`` adds, which give the compact
table one of a few sizes, so the reference compiles once for many seeds.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import numpy as np

from bench import checks, reference


def touched_rows(inputs: Sequence) -> np.ndarray:
    """Sorted ids of every entity row the steps read ((queries, pos, neg)
    per step)."""
    ids = []
    for queries, pos, neg in inputs:
        ids += [np.asarray(q.anchors).ravel() for q in queries]
        ids += [np.asarray(pos).ravel(), np.asarray(neg).ravel()]
    return np.unique(np.concatenate(ids).astype(np.int64))


def padded(rows: np.ndarray, n_entities: int) -> np.ndarray:
    """``rows`` with the lowest untouched ids added up to the next multiple
    of 2**(floor(log2 len) - 4): at most a sixteenth more rows."""
    n = len(rows)
    step = 1 << max(int(n).bit_length() - 5, 0)
    target = min(-(-n // step) * step, n_entities)
    extra = np.setdiff1d(np.arange(target), rows)[:target - n]
    return np.union1d(rows, extra)


def compact_inputs(inputs: Sequence, rows: np.ndarray):
    """The steps as the reference takes them, entity ids renumbered to their
    place in ``rows``."""
    out = []
    for queries, pos, neg in inputs:
        qs = [(pat, np.searchsorted(rows, anchors), rels)
              for pat, anchors, rels in checks.as_queries(queries)]
        out.append((qs, np.searchsorted(rows, pos),
                    np.searchsorted(rows, neg)))
    return out


def compact_params(params: Dict, rows: np.ndarray, device) -> Dict:
    """``rows`` of the entity table and every other leaf whole, on
    ``device``."""
    out = {k: jax.device_put(v, device) for k, v in params.items()
           if k != "entity"}
    out["entity"] = jax.device_put(params["entity"][rows], device)
    return out


def reference_readings(cfg: Dict, seed: int, inputs: Sequence,
                       init: Callable, device=None,
                       precision: str = "highest", keep: float = 1.0
                       ) -> Dict:
    """``checks.reference_readings`` on the touched rows: losses, first
    gradient's leaf norms and the change's leaf norms. ``init(cfg, seed)``
    gives the cell's weights (sharded or not); only the touched rows and the
    other leaves are kept of them."""
    hp = cfg["trainer"]["adam"]
    touched = touched_rows(inputs)
    rows = padded(touched, cfg["graph"]["n_entities"])
    device = device or jax.devices()[0]
    full = init(cfg, seed)
    p0 = compact_params(full, rows, device)
    del full
    p, state, losses, grad1 = p0, None, [], None
    with jax.default_device(device):
        for queries, pos, neg in compact_inputs(inputs, rows):
            loss, g = reference.loss_and_grads(cfg, p, queries, pos, neg,
                                               precision=precision, keep=keep)
            if grad1 is None:
                grad1 = checks._norms(g)
            p, state = reference.adam(p, g, state, hp)
            losses.append(loss)
        change = checks._norms(jax.tree.map(
            lambda a, b: a.astype(np.float32) - b, p, p0))
    return {"losses": losses, "grad1": grad1, "change": change,
            "rows": int(len(touched))}
