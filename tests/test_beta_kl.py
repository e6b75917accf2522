"""BetaE's Beta-KL: the series trigamma, and the closed-form gradient against
autodiff of the plain expression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.special as sp
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.scipy.special import betaln, digamma

from repro.models import ModelConfig, make_model
from repro.models.betae import _trigamma

DIM = 16


@pytest.mark.parametrize("grid", ["linear", "geometric"])
def test_trigamma_matches_scipy_float64(grid):
    space = np.linspace if grid == "linear" else np.geomspace
    x = space(0.05, 80.0, 200_001).astype(np.float32)
    want = sp.polygamma(1, x.astype(np.float64))
    got = np.asarray(jax.jit(_trigamma)(jnp.asarray(x)), np.float64)
    assert np.max(np.abs(got - want) / want) <= 1e-6


def _oracle_distance(model, params, q, ev):
    """The Beta-KL distance as autodiff sees it without a custom gradient:
    ``betaln`` and ``digamma`` from ``jax.scipy.special``."""
    ae, be = model._split(model.entity_state(params, ev))
    aq, bq = model._split(q)
    aq, bq = jnp.clip(aq, 0.05, 40.0), jnp.clip(bq, 0.05, 40.0)
    kl = (
        betaln(aq, bq)
        - betaln(ae, be)
        + (ae - aq) * digamma(ae)
        + (be - bq) * digamma(be)
        + (aq - ae + bq - be) * digamma(ae + be)
    )
    return jnp.sum(kl, axis=-1) / jnp.sqrt(model.cfg.dim)


def _inputs(model, params, layout):
    """q [B,1,2d] against ev [B,M,d] (training) or [1,E,d] (score_all), with
    arguments at both clip edges and one entity equal to its query."""
    rng = np.random.default_rng(7)
    b, m = 4, 9
    ev = rng.normal(0.0, 1.5, (b, m, DIM) if layout == "train" else (1, m, DIM))
    ev[..., :2] = [20.0, -20.0]          # alpha, beta at 40 / 0.05 and back
    ev = jnp.asarray(ev, jnp.float32)
    q = rng.uniform(0.05, 40.0, (b, 1, 2 * DIM))
    q[..., 2:4] = [0.05, 40.0]           # alpha at both edges
    q[..., DIM + 2:DIM + 4] = [40.0, 0.05]
    q = jnp.asarray(q, jnp.float32)
    # near-equal entity and query: KL ~ 0 in this slot
    q = q.at[0, 0].set(model.entity_state(params, ev[0, 0]))
    w = jnp.asarray(rng.uniform(0.5, 1.5, (b, m)), jnp.float32)
    return q, ev, w


@pytest.mark.parametrize("layout", ["train", "score_all"])
def test_distance_and_grads_match_autodiff_oracle(layout):
    model = make_model("betae", ModelConfig(dim=DIM))
    params = model.init_params(jax.random.PRNGKey(0), 50, 6)
    q, ev, w = _inputs(model, params, layout)

    def loss(dist):
        return lambda q, ev: jnp.sum(w * dist(params, q, ev))

    got = jax.jit(jax.value_and_grad(loss(model.distance), (0, 1)))(q, ev)
    want = jax.jit(jax.value_and_grad(
        loss(lambda p, q, ev: _oracle_distance(model, p, q, ev)), (0, 1)))(q, ev)
    d_got = np.asarray(model.distance(params, q, ev))
    d_want = np.asarray(_oracle_distance(model, params, q, ev))
    assert d_got.shape == (4, 9)
    # f32 throughout: betaln's three lgamma terms reach ~270 at 40 + 40 and
    # cancel to the KL, and the series trigamma is within 3e-7 of float64
    # where XLA's polygamma is within 9e-7. Both sides round at ~1e-7 of
    # the largest term, so 1e-5 of each array's largest entry bounds them.
    for a, b in [(d_got, d_want), (got[0], want[0]),
                 (got[1][0], want[1][0]), (got[1][1], want[1][1])]:
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * np.abs(b).max())
    # the clip keeps its zero gradient: q entries outside [0.05, 40] get none
    below = jnp.full_like(q, 0.01)
    g_below = jax.grad(loss(model.distance))(below, ev)
    assert not np.asarray(g_below).any()


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else [v]:
                if isinstance(sub, ClosedJaxpr):
                    _primitives(sub.jaxpr, out)
                elif isinstance(sub, Jaxpr):
                    _primitives(sub, out)
    return out


def test_betae_training_grad_has_no_polygamma_or_zeta():
    model = make_model("betae", ModelConfig(dim=8))
    params = model.init_params(jax.random.PRNGKey(0), 50, 6)
    q = model.embed(params, jnp.array([4, 7, 9]))
    ids = jnp.array([[0, 1, 2], [3, 4, 5], [6, 7, 8]])

    def loss(params, dist=None):
        if dist is None:
            return jnp.sum(model.score_ids(params, q, ids))
        ev = model.fused_entity_vec(params, ids)
        return jnp.sum(dist(params, q[:, None, :], ev))

    assert "custom_vjp_call" in _primitives(jax.make_jaxpr(loss)(params).jaxpr, set())
    prims = _primitives(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, set())
    assert "digamma" in prims and not {"polygamma", "zeta"} & prims
    # the walk sees them where autodiff differentiates digamma itself
    oracle = lambda p: loss(p, lambda p, q, ev: _oracle_distance(model, p, q, ev))
    assert "polygamma" in _primitives(jax.make_jaxpr(jax.grad(oracle))(params).jaxpr, set())
