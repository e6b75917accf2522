"""Pallas TPU kernel: GPU(HBM)-resident semantic integration (Eq. 11 + 12).

e_fused = sigmoid(W_f [h_str[ids] ⊕ (h_sem[sem_ids] W_p + b_p)] + b_f) * 2 - 1

The tables stay in HBM; each grid step DMAs only the tile that holds the row
it needs into VMEM, using scalar-prefetched indices (PrefetchScalarGridSpec) —
the TPU analogue of the paper's "high-speed tensor indexing" gather: the
semantic manifold is never densified or round-tripped, and the projection +
concat + affine + activation all happen in VMEM right after the row DMA.

Two scalar-prefetch index streams because the semantic table may be the
out-of-core HOT-SET CACHE (DESIGN.md §SemanticStore): there ``h_sem`` is the
bounded ``sem_cache`` buffer and ``sem_ids`` are cache SLOTS
(``sem_slot[ids]``), distinct from the structural entity ids. In the
full-resident layout both streams carry the same entity ids.

``rows`` selects the launch geometry (the autotuner's knob — DESIGN.md
§Autotuner):

* ``rows=1`` — the scalar-prefetch gather above: grid ``(n,)``, one DMA of
  the aligned sublane tile holding each row, addressed by the prefetched
  index streams, and the row picked out in VMEM. One grid step per output
  row; each step reads a whole tile (8 f32 rows, 16 bf16 rows), 8-16x the
  bytes of the row it uses. Mosaic refuses the one-row alternatives: a
  ``(1, d)`` or squeezed ``(None, d)`` row block, and a one-row manual DMA
  slice of a ``pl.ANY`` table.
* ``rows>1`` — blocked: the row gathers run as XLA takes (arbitrary-row
  multi-height DMA is not expressible as a single BlockSpec index_map),
  then ONE fuse kernel processes ``rows`` gathered rows per grid step —
  ``n/rows`` launches amortize the per-step overhead that dominates small
  fused dims.

Both paths run the same ``_fuse_block`` body on [rows, ·] f32 tiles, so the
per-row arithmetic — and therefore the output bits — is identical; the
autotuner verifies exactly that before timing a candidate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fuse_block(h, z, wp_ref, bp_ref, wf_ref, bf_ref, o_ref):
    """Shared Eq. 11+12 body: h [rows, d] structural, z [rows, dl] semantic
    (already gathered into VMEM) -> o_ref [rows, d]."""
    zp = (
        jax.lax.dot_general(z, wp_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + bp_ref[...].astype(jnp.float32)
    )                                                        # [rows, dp]
    x = jnp.concatenate([h, zp], axis=-1)                    # [rows, d+dp]
    y = (
        jax.lax.dot_general(x, wf_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + bf_ref[...].astype(jnp.float32)
    )
    o_ref[...] = (jax.nn.sigmoid(y) * 2.0 - 1.0).astype(o_ref.dtype)


def _pick_row(tile_ref, r):
    """Row ``r`` of an aligned [tile_rows, w] VMEM tile as [1, w] f32. A
    one-hot ``where`` + sum adds only zeros to the picked row, so the row's
    bits are unchanged. In the table's partial last tile the rows past its
    end are never picked, and ``where`` keeps whatever they hold (NaN
    included) out of the sum."""
    t = tile_ref[...].astype(jnp.float32)
    hit = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0) == r
    return jnp.sum(jnp.where(hit, t, 0.0), axis=0, keepdims=True)


def _gather_fuse_kernel(ids_ref, sem_ids_ref, hstr_ref, hsem_ref, wp_ref,
                        bp_ref, wf_ref, bf_ref, o_ref, *, tr_str: int,
                        tr_sem: int):
    i = pl.program_id(0)
    _fuse_block(_pick_row(hstr_ref, ids_ref[i] % tr_str),
                _pick_row(hsem_ref, sem_ids_ref[i] % tr_sem),
                wp_ref, bp_ref, wf_ref, bf_ref, o_ref)


def _fuse_only_kernel(hstr_ref, hsem_ref, wp_ref, bp_ref, wf_ref, bf_ref,
                      o_ref):
    _fuse_block(hstr_ref[...].astype(jnp.float32),
                hsem_ref[...].astype(jnp.float32),
                wp_ref, bp_ref, wf_ref, bf_ref, o_ref)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def gather_fuse_pallas(
    ids: jnp.ndarray,      # [n] int32 — row indices into h_str
    h_str: jnp.ndarray,    # [E, d]
    h_sem: jnp.ndarray,    # [E, dl] full H_sem, or [budget, dl] hot-set cache
    wp: jnp.ndarray,       # [dl, dp]
    bp: jnp.ndarray,       # [dp]
    wf: jnp.ndarray,       # [d+dp, d]
    bf: jnp.ndarray,       # [d]
    sem_ids: jnp.ndarray = None,  # [n] int32 rows into h_sem (cache slots);
    #                               None = same as ``ids`` (full-resident)
    *,
    rows: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    n = ids.shape[0]
    E, d = h_str.shape
    _, dl = h_sem.shape
    dp = wp.shape[1]
    if rows < 1:
        raise ValueError(f"rows must be >= 1, got rows={rows}")
    if n % rows != 0:
        raise ValueError(
            f"gather_fuse: ids length n={n} must be a multiple of the row "
            f"block rows={rows} (the ops.gather_fuse wrapper pads for you)")
    if wf.shape[0] != d + dp:
        raise ValueError(
            f"gather_fuse: fuse weight rows {wf.shape[0]} != d+dp = "
            f"{d}+{dp} = {d + dp}")
    if sem_ids is None:
        sem_ids = ids
    if sem_ids.shape != ids.shape:
        raise ValueError(
            f"gather_fuse: sem_ids shape {sem_ids.shape} != ids shape "
            f"{ids.shape}")

    if rows > 1:
        # Blocked path: gather XLA-side (dynamic rows), fuse in [rows, ·]
        # tiles — grid (n/rows,). Same _fuse_block arithmetic per row.
        hs = h_str[ids]                                     # [n, d]
        zs = h_sem[sem_ids]                                 # [n, dl]
        return pl.pallas_call(
            _fuse_only_kernel,
            grid=(n // rows,),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i: (i, 0)),
                pl.BlockSpec((rows, dl), lambda i: (i, 0)),
                pl.BlockSpec((dl, dp), lambda i: (0, 0)),
                pl.BlockSpec((1, dp), lambda i: (0, 0)),
                pl.BlockSpec((d + dp, d), lambda i: (0, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, d), h_str.dtype),
            interpret=interpret,
        )(hs, zs, wp, bp.reshape(1, dp), wf, bf.reshape(1, d))

    # rows == 1: scalar-prefetch gather, one output row per grid step.
    # Mosaic moves whole sublane tiles (8 rows of f32, 16 of bf16), so each
    # step DMAs the aligned tile holding row ids[i] (block index
    # ids[i] // tile_rows) and _pick_row selects the row in VMEM. The
    # output is laid out [n, 1, d] so its (1, d) block spans the full last
    # two dims; the reshape back to [n, d] costs one copy of the n rows.
    tr_str = 32 // h_str.dtype.itemsize
    tr_sem = 32 // h_sem.dtype.itemsize

    def str_map(i, ids_ref, sem_ids_ref):
        return (ids_ref[i] // tr_str, 0)

    def sem_map(i, ids_ref, sem_ids_ref):
        return (sem_ids_ref[i] // tr_sem, 0)

    def rep_map(i, ids_ref, sem_ids_ref):
        return (0, 0)

    out = pl.pallas_call(
        functools.partial(_gather_fuse_kernel, tr_str=tr_str, tr_sem=tr_sem),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((tr_str, d), str_map),
                pl.BlockSpec((tr_sem, dl), sem_map),
                pl.BlockSpec((dl, dp), rep_map),
                pl.BlockSpec((1, dp), rep_map),
                pl.BlockSpec((d + dp, d), rep_map),
                pl.BlockSpec((1, d), rep_map),
            ],
            out_specs=pl.BlockSpec(
                (None, 1, d), lambda i, ids_ref, sem_ids_ref: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), h_str.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), sem_ids.astype(jnp.int32),
      h_str, h_sem, wp, bp.reshape(1, dp), wf, bf.reshape(1, d))
    return out.reshape(n, d)
