"""Plain reference of BetaE (Ren & Leskovec, 2020) at the program's layout.

State = (alpha, beta), each ``dim`` wide. An entity row ``e`` lifts to
``alpha = clip(softplus(2e) + .05)``, ``beta = clip(softplus(-2e) + .05)``
with ``clip`` to [0.05, 40]. Projection: a two-layer ReLU MLP over
``[state, r]`` (3 dim -> hidden -> 2 dim), then ``clip(softplus(y) + .05)``.
Intersection and union: attention weights ``softmax_k(MLP(x_k))`` (2 dim ->
hidden -> 1), a weighted sum over k, clipped; union has its own attention
MLP. Negation: ``clip(1 / max(x, .05))``. Distance: KL(Beta(entity) ||
Beta(query)) summed over dims, over sqrt(dim); ``betaln`` is built from
JAX's ``gammaln`` and ``digamma`` is written out below (recurrence plus
asymptotic series), so the reference calls neither of the functions the
program takes from ``jax.scipy.special``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln

_EPS = 0.05
_MAXP = 40.0


def param_shapes(m, n_entities: int, n_relations: int):
    d, h = m["dim"], m["dim"] * m["hidden_mult"]
    shapes = {"entity": (n_entities, d), "relation": (n_relations, d),
              "proj_w0": (3 * d, h), "proj_b0": (h,),
              "proj_w1": (h, 2 * d), "proj_b1": (2 * d,)}
    for name in ("att", "uatt"):
        shapes.update({f"{name}_w0": (2 * d, h), f"{name}_b0": (h,),
                       f"{name}_w1": (h, 1), f"{name}_b1": (1,)})
    return shapes


def state_dim(m) -> int:
    return 2 * m["dim"]


def _clip(x):
    return jnp.clip(x, _EPS, _MAXP)


def _mlp(p, prefix, x):
    x = jax.nn.relu(x @ p[prefix + "_w0"] + p[prefix + "_b0"])
    return x @ p[prefix + "_w1"] + p[prefix + "_b1"]


def digamma(x):
    """psi(x) for x > 0: shift up by 6 with the recurrence
    psi(x) = psi(x + 1) - 1/x, then the asymptotic series."""
    acc = jnp.zeros_like(x)
    for _ in range(6):
        acc = acc - 1.0 / x
        x = x + 1.0
    r = 1.0 / (x * x)
    series = (jnp.log(x) - 0.5 / x
              - r * (1.0 / 12 - r * (1.0 / 120 - r * (1.0 / 252
                                                      - r * (1.0 / 240)))))
    return acc + series


def betaln(a, b):
    return gammaln(a) + gammaln(b) - gammaln(a + b)


def entity_state(m, p, ev):
    a = _clip(jax.nn.softplus(ev * 2.0) + _EPS)
    b = _clip(jax.nn.softplus(-ev * 2.0) + _EPS)
    return jnp.concatenate([a, b], axis=-1)


def project(m, p, x, rel_ids):
    y = _mlp(p, "proj", jnp.concatenate([x, p["relation"][rel_ids]], axis=-1))
    return _clip(jax.nn.softplus(y) + _EPS)


def _attend(p, prefix, xs):                  # xs [n, k, 2d]
    w = jax.nn.softmax(_mlp(p, prefix, xs), axis=1)
    return _clip(jnp.sum(w * xs, axis=1))


def intersect(m, p, xs):
    return _attend(p, "att", xs)


def union(m, p, xs):
    return _attend(p, "uatt", xs)


def negate(m, p, x):
    return _clip(1.0 / jnp.maximum(x, _EPS))


def distance(m, p, q, ev):                   # q [.., 2d], ev [.., d]
    d = m["dim"]
    s = entity_state(m, p, ev)
    ae, be = s[..., :d], s[..., d:]
    aq, bq = _clip(q[..., :d]), _clip(q[..., d:])
    kl = (betaln(aq, bq) - betaln(ae, be)
          + (ae - aq) * digamma(ae) + (be - bq) * digamma(be)
          + (aq - ae + bq - be) * digamma(ae + be))
    return jnp.sum(kl, axis=-1) / jnp.sqrt(float(d))
