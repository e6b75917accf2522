"""The plain reference the comparison that decides ``correct`` runs.

It imports nothing of the program. A query is encoded straight from its
template tree (``harness.TEMPLATES``), one pattern at a time over fixed-size
chunks of rows, with the family's operators from ``bench/ref/<family>.py``;
the loss is the negative-sampling log-sigmoid loss of the paper's Eq. 6,
and Adam is written out. Chunks have fixed shapes, so every seed runs the
same compiled programs.

``precision`` "highest" is the reference: float32 with exact float32
matmuls. "bfloat16" is the control: params and arithmetic in bfloat16, the
nearest precision below what the configurations state (float32 at the
TPU's default matmul precision).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness

# A query as the reference takes it: (pattern, anchors [na], relations [nr]).
Query = Tuple[str, np.ndarray, np.ndarray]


def family(name: str):
    return harness.load_module("ref", name)


def _dtype(precision: str):
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


def _matmul_precision(precision: str) -> str:
    return "highest" if precision == "highest" else "default"


@functools.lru_cache(maxsize=None)
def _init_fn(family_name: str, m_items: tuple, n_entities: int,
             n_relations: int):
    fam = family(family_name)
    m = dict(m_items)
    shapes = fam.param_shapes(m, n_entities, n_relations)

    def init(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, shape) in zip(keys, sorted(shapes.items())):
            if name in ("entity", "relation"):
                out[name] = jax.random.normal(k, shape) / np.sqrt(m["dim"])
            elif len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                scale = np.sqrt(2.0 / (shape[0] + shape[1]))
                out[name] = jax.random.normal(k, shape) * scale
        return out

    return jax.jit(init)


def init_params(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The cell's weights from ``seed``, on the device, in one jitted call:
    tables ~ N(0, 1/dim), matrices Glorot-normal, biases zero."""
    g = cfg["graph"]
    fn = _init_fn(cfg["family"], tuple(sorted(cfg["model"].items())),
                  g["n_entities"], g["n_relations"])
    key = jax.random.PRNGKey(harness.derive_seed(seed, "params"))
    return fn(key)


# --------------------------------------------------------------------- encode
def _encode(fam, m, p, pattern: str, anchors, rels):
    nodes = harness.TEMPLATES[pattern]
    states: List = []
    a_i = r_i = 0
    for op, inputs in nodes:
        if op == "E":
            y = fam.entity_state(m, p, p["entity"][anchors[:, a_i]])
            a_i += 1
        elif op == "P":
            y = fam.project(m, p, states[inputs[0]], rels[:, r_i])
            r_i += 1
        elif op == "N":
            y = fam.negate(m, p, states[inputs[0]])
        elif op == "I":
            y = fam.intersect(m, p, jnp.stack([states[j] for j in inputs], 1))
        else:
            y = fam.union(m, p, jnp.stack([states[j] for j in inputs], 1))
        states.append(y)
    return states[-1]


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@functools.lru_cache(maxsize=None)
def _chunk_fns(family_name: str, m_items: tuple, precision: str):
    fam = family(family_name)
    m = dict(m_items)
    dt = _dtype(precision)

    def loss_sum(p, pattern, anchors, rels, pos, neg, mask):
        q = _encode(fam, m, p, pattern, anchors, rels)
        cand = jnp.concatenate([pos[:, None], neg], axis=1)
        s = m["gamma"] - fam.distance(m, p, q[:, None, :], p["entity"][cand])
        per = (-jax.nn.log_sigmoid(s[:, 0])
               - jnp.mean(jax.nn.log_sigmoid(-s[:, 1:]), axis=1))
        return jnp.sum(per * mask.astype(per.dtype))

    grad = jax.jit(jax.value_and_grad(loss_sum), static_argnums=1)

    def scores(p, pattern, anchors, rels):
        q = _encode(fam, m, p, pattern, anchors, rels)
        ev = p["entity"]
        return (m["gamma"] - fam.distance(m, p, q[:, None, :], ev[None])
                ).astype(jnp.float32)

    return grad, jax.jit(scores, static_argnums=1), dt


def _chunks(queries: Sequence[Query], chunk: int):
    """(pattern, row indices [chunk], mask [chunk]) per fixed-size chunk;
    short chunks repeat their first row under mask 0."""
    by_pattern: Dict[str, List[int]] = {}
    for i, (pat, _, _) in enumerate(queries):
        by_pattern.setdefault(pat, []).append(i)
    for pat in sorted(by_pattern):
        rows = by_pattern[pat]
        for lo in range(0, len(rows), chunk):
            part = rows[lo:lo + chunk]
            mask = np.zeros(chunk, np.float32)
            mask[:len(part)] = 1.0
            idx = np.array(part + [part[0]] * (chunk - len(part)))
            yield pat, idx, mask


def _stack(queries, idx, which):
    return np.stack([queries[i][which] for i in idx]).astype(np.int32)


def loss_and_grads(cfg: Dict, params, queries: Sequence[Query], pos, neg,
                   precision: str = "highest", chunk: int = 64,
                   keep: float = 1.0):
    """Mean loss over the batch and its gradient. ``keep`` < 1 takes the
    mean over the first ``keep`` share of the rows only (a planted fault)."""
    grad_fn, _, dt = _chunk_fns(cfg["family"],
                                tuple(sorted(cfg["model"].items())), precision)
    p = _cast(params, dt)
    n_rows = len(queries)
    kept = max(1, int(round(n_rows * keep)))
    total, grads = 0.0, None
    with jax.default_matmul_precision(_matmul_precision(precision)):
        for pat, idx, mask in _chunks(queries, chunk):
            mask = mask * (idx < kept)
            if not mask.any():
                continue
            val, g = grad_fn(p, pat, _stack(queries, idx, 1),
                             _stack(queries, idx, 2),
                             jnp.asarray(pos[idx], jnp.int32),
                             jnp.asarray(neg[idx], jnp.int32),
                             jnp.asarray(mask, dt))
            total = total + val.astype(jnp.float32)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    scale = 1.0 / kept
    return (float(total) * scale,
            jax.tree.map(lambda x: x * jnp.asarray(scale, x.dtype), grads))


def adam(params, grads, state, hp: Dict):
    """One Adam step: (new params, new state). ``state`` None starts it."""
    if state is None:
        state = {"m": jax.tree.map(jnp.zeros_like, params),
                 "v": jax.tree.map(jnp.zeros_like, params), "t": 0}
    t = state["t"] + 1
    b1, b2 = hp["b1"], hp["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
        return p - hp["lr"] * (step + hp["weight_decay"] * p)

    return (jax.tree.map(upd, params, m, v), {"m": m, "v": v, "t": t})


def score_all(cfg: Dict, params, queries: Sequence[Query],
              precision: str = "highest", chunk: int = 16) -> np.ndarray:
    """Scores of every query against every entity, [n, E] float32."""
    _, score_fn, dt = _chunk_fns(cfg["family"],
                                 tuple(sorted(cfg["model"].items())),
                                 precision)
    p = _cast(params, dt)
    out = np.zeros((len(queries), p["entity"].shape[0]), np.float32)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        for pat, idx, mask in _chunks(queries, chunk):
            s = np.asarray(score_fn(p, pat, _stack(queries, idx, 1),
                                    _stack(queries, idx, 2)))
            out[idx[mask > 0]] = s[mask > 0]
    return out
