"""Mixture-of-Experts FFN with sort-based pool dispatch.

DESIGN.md §Arch-applicability: token→expert dispatch is the paper's
operator-pool batching applied at the layer level — experts are operator
types, tokens are ready operators, and the capacity factor plays the role of
B_max in the Max-Fillness policy (overflowing tokens are dropped, i.e. the
pool executes at its fill limit). The packing below is the same
sort-by-type → dense-batch → scatter-back mechanism as repro/core's executor.

Sharding: computed inside shard_map so the token sort stays *local* to each
data shard (a global sharded argsort would lower to a distributed sort).
Two expert-sharding modes over the ``model`` axis:
  * tp — every shard holds all experts' F/m slice; partial outputs psum'd.
  * ep — every shard holds E/m full experts; only local experts' outputs are
         accumulated, then psum'd (requires E % m == 0, e.g. jamba's 16).
Both modes do identical FLOPs/chip; they differ in weight layout, einsum
shapes and collective pattern — which one wins is a §Perf question.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def pack_by_expert(x, expert_idx, gates, n_experts: int, capacity: int):
    """Sort-based pool packing. x [T, D]; expert_idx/gates [T, k].

    Returns (packed [E, C, D], combine metadata). Overflow beyond capacity is
    dropped (Max-Fillness at the fill limit).

    §Perf iteration 2: both directions are GATHER-based. Only tiny int32
    index/mask tensors are scattered; the [E, C, D] activations are built by
    gather + mask, and the combine reads y by gather + segment-sum over the
    token-major (T, k) layout — no [E*C, D]-sized scatter(-add) or zero-init
    passes through HBM."""
    T, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    starts = jnp.searchsorted(se, jnp.arange(n_experts))
    pos = jnp.arange(T * k) - starts[se]
    keep = pos < capacity
    dest = jnp.where(keep, se * capacity + pos, n_experts * capacity)  # trash slot
    # tiny scatters: which token fills each expert slot (and whether any does)
    ec = n_experts * capacity
    gather_idx = jnp.zeros((ec + 1,), jnp.int32).at[dest].set(st.astype(jnp.int32))
    filled = jnp.zeros((ec + 1,), bool).at[dest].set(keep)
    packed = jnp.where(filled[:ec, None], x[gather_idx[:ec]], 0)
    # invert the sort so combine can walk (t, k) order directly
    dest_by_flat = jnp.zeros((T * k,), jnp.int32).at[order].set(dest.astype(jnp.int32))
    return packed.reshape(n_experts, capacity, -1), (dest_by_flat, gates, T, k)


def combine_from_experts(y, meta, T: int):
    """Inverse of pack_by_expert with gate weighting. y [E, C, D]."""
    dest_by_flat, gates, T_, k = meta
    e, c, d = y.shape
    y_flat = y.reshape(e * c, d)
    safe = jnp.minimum(dest_by_flat, e * c - 1)
    vals = jnp.where((dest_by_flat < e * c)[:, None], y_flat[safe], 0)
    vals = vals * gates.reshape(T_ * k, 1).astype(y.dtype)
    return vals.reshape(T_, k, d).sum(axis=1)


def _moe_local(x, router, w_gate, w_up, w_down, *, n_experts, top_k,
               capacity_factor, mode, model_axis: Optional[str], ep_shards: int):
    """Per-shard MoE body. x [T_local, D]; weights are the local slices."""
    T, D = x.shape
    logits = (x.astype(jnp.float32)) @ router.astype(jnp.float32)      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    capacity = max(int(np.ceil(T * top_k / n_experts * capacity_factor)), 1)

    packed, meta = pack_by_expert(x, eidx, gates, n_experts, capacity)  # [E, C, D]
    if mode == "ep" and model_axis is not None:
        # Local shard computes only its E/m experts (full F); the other
        # experts' token rows combine to zero locally and are filled in by
        # the POST-COMBINE psum (see below).
        e_loc = n_experts // ep_shards
        shard = jax.lax.axis_index(model_axis)
        packed_loc = jax.lax.dynamic_slice_in_dim(packed, shard * e_loc, e_loc, 0)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", packed_loc, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", packed_loc, w_up
        )
        y_loc = jnp.einsum("ecf,efd->ecd", h, w_down)
        y = jnp.zeros((n_experts, capacity, D), y_loc.dtype)
        y = jax.lax.dynamic_update_slice_in_dim(y, y_loc, shard * e_loc, 0)
    else:
        # TP-in-expert: all experts, F/m slice each; outputs are partial sums.
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", packed, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", packed, w_up
        )
        y = jnp.einsum("ecf,efd->ecd", h, w_down)
    out = combine_from_experts(y.astype(x.dtype), meta, T)   # [T_local, D]
    if model_axis is not None:
        # §Perf iteration 1: psum AFTER the combine. Both modes produce
        # outputs that are linear in the per-shard contributions, so reducing
        # the combined [T, D] instead of the dispatched [E, C, D] is exact
        # and shrinks the payload by E*C/T = top_k*capacity_factor (~2.5x)
        # ... and far more when capacity padding is loose.
        out = jax.lax.psum(out, model_axis)
    return out


def moe_ffn(x, router, w_gate, w_up, w_down, cfg, mesh=None,
            dp_axes: Tuple[str, ...] = ()) -> jnp.ndarray:
    """x [B, S, D] (or [T, D]). Weights: router [D, E]; w_* [E, D, F]/[E, F, D]."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    kw = dict(
        n_experts=cfg.n_experts,
        top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
        mode=cfg.moe_mode,
    )
    if mesh is None:
        out = _moe_local(x2, router, w_gate, w_up, w_down, model_axis=None,
                         ep_shards=1, **kw)
        return out.reshape(shape)

    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    # Drop DP axes that do not divide the token count (e.g. batch=1 decode):
    # tokens are then replicated over those axes, which is what the incoming
    # activation sharding already is.
    while dp and x2.shape[0] % int(np.prod([mesh.shape[a] for a in dp])) != 0:
        dp = dp[:-1]
    m = mesh.shape["model"]
    if cfg.moe_mode == "ep":
        assert cfg.n_experts % m == 0, (cfg.n_experts, m)
        w_specs = (P("model", None, None), P("model", None, None), P("model", None, None))
    else:
        w_specs = (P(None, None, "model"), P(None, None, "model"), P(None, "model", None))
    fn = jax.shard_map(
        functools.partial(_moe_local, model_axis="model", ep_shards=m, **kw),
        mesh=mesh,
        in_specs=(P(dp, None), P(None, None)) + w_specs,
        out_specs=P(dp, None),
        check_vma=False,
    )
    return fn(x2, router, w_gate, w_up, w_down).reshape(shape)
