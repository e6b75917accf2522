"""BetaE (Ren & Leskovec, 2020): Beta-distribution embeddings with closed-form
negation (reciprocal parameters) and attention-weighted intersection."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.lax import lgamma
from jax.scipy.special import digamma

from repro.models.base import ModelConfig, QueryEncoder, mlp_apply, mlp_params, register_model

_EPS = 0.05
_MAXP = 40.0


def _clip(p):
    return jnp.clip(p, _EPS, _MAXP)


def _betaln(x, y):
    return lgamma(x) + lgamma(y) - lgamma(x + y)


def _trigamma(x):
    """psi'(x) for x > 0: six steps of psi'(x) = psi'(x + 1) + 1/x^2, then
    the asymptotic series. Arithmetic only: no polygamma, no zeta."""
    acc = jnp.zeros_like(x)
    for _ in range(6):
        acc = acc + 1.0 / (x * x)
        x = x + 1.0
    r = 1.0 / x
    r2 = r * r
    series = r + r2 * (0.5 + r * (1.0 / 6 + r2 * (-1.0 / 30 + r2 * (
        1.0 / 42 - r2 * (1.0 / 30)))))
    return acc + series


def _sum_to(g, shape):
    """Sum ``g`` back to ``shape``, over the axes it was broadcast along."""
    lead = g.ndim - len(shape)
    axes = tuple(range(lead)) + tuple(lead + i for i, n in enumerate(shape) if n == 1)
    return jnp.sum(g, axis=axes).reshape(shape)


def _kl_terms(aq, bq, ae, be):
    pa, pb, pab = digamma(ae), digamma(be), digamma(ae + be)
    kl = (
        _betaln(aq, bq)
        - _betaln(ae, be)
        + (ae - aq) * pa
        + (be - bq) * pb
        + (aq - ae + bq - be) * pab
    )
    return kl, (pa, pb, pab)


@jax.custom_vjp
def _beta_kl(aq, bq, ae, be):
    """Elementwise KL( Beta(ae,be) || Beta(aq,bq) ), broadcasting. Its
    gradient is written in closed form, so autodiff never differentiates
    digamma (whose derivative XLA expands as a Hurwitz zeta)."""
    return _kl_terms(aq, bq, ae, be)[0]


def _beta_kl_fwd(aq, bq, ae, be):
    kl, psi = _kl_terms(aq, bq, ae, be)
    # The barrier keeps the residuals as computed here: without it XLA
    # recomputes two of the three digammas inside the backward's fusion.
    return kl, jax.lax.optimization_barrier((aq, bq, ae, be, *psi))


def _beta_kl_bwd(res, g):
    aq, bq, ae, be, pa, pb, pab = res
    s = aq - ae + bq - be
    t = s * _trigamma(ae + be)
    q_ab = digamma(aq + bq)
    return (
        _sum_to(g * (digamma(aq) - q_ab - pa + pab), aq.shape),
        _sum_to(g * (digamma(bq) - q_ab - pb + pab), bq.shape),
        _sum_to(g * ((ae - aq) * _trigamma(ae) + t), ae.shape),
        _sum_to(g * ((be - bq) * _trigamma(be) + t), be.shape),
    )


_beta_kl.defvjp(_beta_kl_fwd, _beta_kl_bwd)


@register_model("betae")
class BetaE(QueryEncoder):
    @property
    def state_dim(self) -> int:
        return 2 * self.cfg.dim

    def init_geometry(self, key, n_entities, n_relations):
        d, h = self.cfg.dim, self.cfg.dim * self.cfg.hidden_mult
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {"relation": jax.random.normal(k1, (n_relations, d)) * (1.0 / jnp.sqrt(d))}
        p.update(mlp_params(k2, (3 * d, h, 2 * d), "proj"))   # Psi_theta projection MLP
        p.update(mlp_params(k3, (2 * d, h, 1), "att"))        # intersection attention
        p.update(mlp_params(k4, (2 * d, h, 1), "uatt"))       # union mixture attention
        return p

    def _split(self, s):
        d = self.cfg.dim
        return s[..., :d], s[..., d:]

    def entity_state(self, params, ent_vec):
        # Sufficient statistics from the joint embedding (Eq. 3): the fused
        # vector parameterizes (alpha, beta) via a smooth positive map.
        a = _clip(jax.nn.softplus(ent_vec * 2.0) + _EPS)
        b = _clip(jax.nn.softplus(-ent_vec * 2.0) + _EPS)
        return jnp.concatenate([a, b], axis=-1)

    def project(self, params, x, rel_ids):
        r = params["relation"][rel_ids]
        y = mlp_apply(params, "proj", jnp.concatenate([x, r], axis=-1), 2)
        return _clip(jax.nn.softplus(y) + _EPS)

    def _attn_combine(self, params, X, prefix):
        if self.cfg.use_pallas:
            # cardinality-class fused kernel (one VMEM pass per class, Eq. 8/9)
            from repro.kernels import ops as kops

            return _clip(kops.intersect(
                X, params[f"{prefix}_w0"], params[f"{prefix}_b0"],
                params[f"{prefix}_w1"], params[f"{prefix}_b1"]))
        w = jax.nn.softmax(mlp_apply(params, prefix, X, 2), axis=1)  # [n, k, 1]
        return _clip(jnp.sum(w * X, axis=1))

    def intersect(self, params, X):
        return self._attn_combine(params, X, "att")

    def union(self, params, X):
        # Mixture surrogate (native BetaE rewrites unions to DNF).
        return self._attn_combine(params, X, "uatt")

    def negate(self, params, x):
        return _clip(1.0 / jnp.maximum(x, _EPS))

    def distance(self, params, q, ent_vec):
        ae, be = self._split(self.entity_state(params, ent_vec))
        aq, bq = self._split(q)
        aq, bq = _clip(aq), _clip(bq)
        # KL( Beta(ae,be) || Beta(aq,bq) ), summed over dims.
        kl = _beta_kl(aq, bq, ae, be)
        return jnp.sum(kl, axis=-1) / jnp.sqrt(self.cfg.dim)
