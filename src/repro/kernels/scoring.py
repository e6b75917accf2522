"""Pallas TPU kernel: vectorized scoring logits (Eq. 6), S = gamma ± <Q, E>.

The paper casts the objective as one dense Q·Eᵀ block so the "linear algebra
libraries optimize data reuse via shared memory"; the TPU-native version is an
MXU-blocked matmul with an fp32 VMEM accumulator. Tiles are (bm, bn) output
blocks with a k-loop over the latent dim; every tile dimension is a multiple
of the 128-lane register/MXU width (callers pad via ops.py).

mode="dot" uses the MXU (jnp.dot); mode="l1" computes the translational
distance on the VPU with the same blocking (GQE-style geometries).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scoring_kernel(q_ref, e_ref, o_ref, acc_ref, *, nk: int, gamma: float, mode: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)          # [bm, bk] VMEM tile
    e = e_ref[...].astype(jnp.float32)          # [bn, bk] VMEM tile
    if mode == "dot":
        acc_ref[...] += jnp.dot(q, e.T, preferred_element_type=jnp.float32)
    else:  # l1: -(sum_d |q - e|) accumulated blockwise over d
        acc_ref[...] += -jnp.sum(
            jnp.abs(q[:, None, :] - e[None, :, :]), axis=-1
        )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = (gamma + acc_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("gamma", "mode", "bm", "bn", "bk", "interpret")
)
def scoring_pallas(
    q: jnp.ndarray,
    e: jnp.ndarray,
    *,
    gamma: float = 0.0,
    mode: str = "dot",
    bm: int = 128,
    bn: int = 256,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q [B, d], e [N, d] -> [B, N]. B % bm == N % bn == d % bk == 0."""
    B, d = q.shape
    N, d2 = e.shape
    # Explicit errors (not asserts — those vanish under `python -O`) naming
    # the offending dim and the multiple it must satisfy.
    if d != d2:
        raise ValueError(
            f"scoring: q feature dim d={d} != e feature dim d={d2}")
    if B % bm != 0:
        raise ValueError(
            f"scoring: q rows B={B} must be a multiple of the row tile "
            f"bm={bm} (the ops.scoring wrapper pads for you)")
    if N % bn != 0:
        raise ValueError(
            f"scoring: e rows N={N} must be a multiple of the column tile "
            f"bn={bn} (the ops.scoring wrapper pads for you)")
    if d % bk != 0:
        raise ValueError(
            f"scoring: feature dim d={d} must be a multiple of the k tile "
            f"bk={bk} (the ops.scoring wrapper pads for you)")
    nk = d // bk
    grid = (B // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_scoring_kernel, nk=nk, gamma=gamma, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(q, e)
