"""Plain reference of GQE (Hamilton et al., 2018) at the program's layout.

Translational projection ``x + r``; DeepSets intersection
``mean_k(phi(x_k)) @ W_rho`` with a two-layer ReLU ``phi`` of width
``hidden_mult * dim``; the smooth-max union ``logsumexp(4 x) / 4``; a
two-layer ReLU negation MLP; distance ``|q - e|_1``. Parameter names and
shapes are the program's, so one params dict feeds both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def param_shapes(m, n_entities: int, n_relations: int):
    d, h = m["dim"], m["dim"] * m["hidden_mult"]
    shapes = {"entity": (n_entities, d), "relation": (n_relations, d),
              "int_out_w": (d, d)}
    for name in ("int", "neg"):
        shapes.update({f"{name}_w0": (d, h), f"{name}_b0": (h,),
                       f"{name}_w1": (h, d), f"{name}_b1": (d,)})
    return shapes


def state_dim(m) -> int:
    return m["dim"]


def _mlp(p, prefix, x):
    x = jax.nn.relu(x @ p[prefix + "_w0"] + p[prefix + "_b0"])
    return x @ p[prefix + "_w1"] + p[prefix + "_b1"]


def entity_state(m, p, ev):
    return ev


def project(m, p, x, rel_ids):
    return x + p["relation"][rel_ids]


def intersect(m, p, xs):                     # xs [n, k, d]
    return jnp.mean(_mlp(p, "int", xs), axis=1) @ p["int_out_w"]


def union(m, p, xs):
    return jax.nn.logsumexp(xs * 4.0, axis=1) / 4.0


def negate(m, p, x):
    return _mlp(p, "neg", x)


def distance(m, p, q, ev):                   # q [.., d], ev [.., d]
    return jnp.sum(jnp.abs(q - ev), axis=-1)
