"""Pallas TPU kernel: cardinality-class attention intersection (Eq. 8/9).

One equivalence class C_k (all intersections with the same input cardinality
k) executes as one VMEM-resident fusion: 2-layer MLP attention logits →
softmax over the k inputs → weighted combine. The whole chain — two small
matmuls, softmax, reduce — runs on one [bn, k, d] tile without HBM
round-trips, which is exactly where the paper's 13.1× per-operator win comes
from (fragmented per-query launches → dense class-wide fusion).

k is a *static* kernel parameter (one compiled kernel per equivalence class,
mirroring Eq. 8). The wrapper hands the kernel a k-major [k, n, d] view so
each grid step sees k separate [bn, d] tiles; the softmax and the combine
over k are unrolled in the kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _intersect_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, *, k: int):
    # x_ref is k-major [k, bn, d]: each input of the class is one 2-D
    # [bn, d] tile, so every op below is a plain 2-D matmul or an
    # elementwise op on [bn, ·] — forms Mosaic lowers (a batched
    # "nk,nkd->nd" contraction or a [bn, k, d] -> [bn*k, d] reshape is not).
    w1 = w1_ref[...].astype(jnp.float32)                    # [d, hd]
    b1 = b1_ref[...].astype(jnp.float32)                    # [1, hd]
    w2 = w2_ref[...].astype(jnp.float32)                    # [hd, pad] (col 0 real)
    b2 = b2_ref[...].astype(jnp.float32)                    # [1, pad]
    xs, logits = [], []
    for j in range(k):
        xj = x_ref[j].astype(jnp.float32)                   # [bn, d]
        h = jnp.maximum(
            jax.lax.dot_general(xj, w1, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) + b1,
            0.0)                                             # [bn, hd]
        lj = (jax.lax.dot_general(h, w2, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
              + b2)[:, :1]                                   # [bn, 1]
        xs.append(xj)
        logits.append(lj)
    # Softmax over the k inputs, unrolled: k is a small static class size.
    m = functools.reduce(jnp.maximum, logits)
    ex = [jnp.exp(lj - m) for lj in logits]
    denom = functools.reduce(jnp.add, ex)
    out = functools.reduce(jnp.add, [(e / denom) * xj for e, xj in zip(ex, xs)])
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def intersect_pallas(
    x: jnp.ndarray,   # [n, k, d]
    w1: jnp.ndarray,  # [d, hd]
    b1: jnp.ndarray,  # [hd]
    w2: jnp.ndarray,  # [hd, pad] (col 0 = real logit weights)
    b2: jnp.ndarray,  # [pad]
    *,
    bn: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    n, k, d = x.shape
    hd = w1.shape[1]
    pad = w2.shape[1]
    # Explicit errors (not asserts — those vanish under `python -O`) naming
    # the offending dim and the multiple it must satisfy.
    if n % bn != 0:
        raise ValueError(
            f"intersect: pool rows n={n} must be a multiple of the row tile "
            f"bn={bn} (the ops.intersect wrapper pads for you)")
    if w1.shape[0] != d:
        raise ValueError(
            f"intersect: attention MLP input dim {w1.shape[0]} != state "
            f"dim d={d}")
    if w2.shape[0] != hd:
        raise ValueError(
            f"intersect: logit head input dim {w2.shape[0]} != hidden dim "
            f"hd={hd}")
    grid = (n // bn,)
    xt = jnp.swapaxes(x, 0, 1)                               # [k, n, d]
    return pl.pallas_call(
        functools.partial(_intersect_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, bn, d), lambda i: (0, i, 0)),
            pl.BlockSpec((d, hd), lambda i: (0, 0)),
            pl.BlockSpec((1, hd), lambda i: (0, 0)),
            pl.BlockSpec((hd, pad), lambda i: (0, 0)),
            pl.BlockSpec((1, pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
    )(xt, w1, b1.reshape(1, hd), w2, b2.reshape(1, pad))
