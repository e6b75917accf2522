"""Jitted public wrappers for the Pallas kernels: shape padding to hardware
tiles and dtype handling.

``interpret=None`` (the default) picks interpret mode when JAX's default
backend is not a TPU — the CPU test setting, which runs the kernel body
through the Pallas interpreter and checks its arithmetic but not whether
Mosaic accepts it. Code that must run the compiled kernel passes
``interpret=False`` (``chip_smoke.py`` does), so a run that lands on the
wrong device fails instead of interpreting.

Tile configs resolve through the process autotuner (DESIGN.md §Autotuner):
pass ``bm``/``bn``/``rows`` explicitly to pin a config (the tuner's sweep
does), or leave them ``None`` and the tuned config for the call's shape
bucket is used — falling back to the hand-picked ``autotune.DEFAULTS`` when
nothing is tuned, which reproduces the pre-autotuner behavior bit for bit.
Row padding goes through the ONE shared rule ``autotune.row_block`` — the
same rule the compiler's kernel-aware ``bucket_size`` applies — so the
wrapper and the scheduler can never disagree about a padded size and force
an avoidable retrace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune as at
from repro.kernels import ref
from repro.kernels.autotune import LANE as _LANE
from repro.kernels.gather_fuse import gather_fuse_pallas
from repro.kernels.intersect import intersect_pallas
from repro.kernels.scoring import scoring_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def scoring(q, e, gamma: float = 0.0, mode: str = "dot",
            bm: int | None = None, bn: int | None = None,
            bk: int | None = None, interpret: bool | None = None):
    """Padded/unpadded entry to the scoring kernel. q [B,d], e [N,d]."""
    if interpret is None:
        interpret = not _on_tpu()
    B, d = q.shape
    N = e.shape[0]
    if bm is None or bn is None or bk is None:
        cfg = at.get_tuner().config_for(
            "scoring", at.scoring_bucket(B, N, d), str(q.dtype), interpret)
        bm = cfg["bm"] if bm is None else bm
        bn = cfg["bn"] if bn is None else bn
        bk = cfg["bk"] if bk is None else bk
    bm_, Bp = at.row_block(B, bm, 8)
    bn_, Np = at.row_block(N, bn, _LANE)
    qp = _pad_to(_pad_to(q, 0, bm_), 1, bk)
    ep = _pad_to(_pad_to(e, 0, bn_), 1, bk)
    out = scoring_pallas(qp, ep, gamma=gamma, mode=mode, bm=bm_, bn=bn_, bk=bk,
                         interpret=interpret)
    return out[:B, :N]


def intersect(x, w1, b1, w2, b2, bn: int | None = None,
              interpret: bool | None = None):
    """x [n,k,d], MLP (w1 [d,hd], b1, w2 [hd,1], b2 [1]) -> [n,d]."""
    if interpret is None:
        interpret = not _on_tpu()
    n, k, d = x.shape
    if bn is None:
        cfg = at.get_tuner().config_for(
            "intersect", at.intersect_bucket(n, k, d, w1.shape[1]),
            str(x.dtype), interpret)
        bn = cfg["bn"]
    bn_, _np = at.row_block(n, bn, 8)
    xp = _pad_to(x, 0, bn_)
    # Pad the logit head to a full lane so the tile is hardware-aligned.
    w2p = _pad_to(w2, 1, _LANE)
    b2p = _pad_to(b2, 0, _LANE)
    out = intersect_pallas(xp, w1, b1, w2p, b2p, bn=bn_, interpret=interpret)
    return out[:n]


def gather_fuse(ids, h_str, h_sem, wp, bp, wf, bf, sem_ids=None,
                rows: int | None = None, interpret: bool | None = None):
    """ids [n] -> fused entity vectors [n, d] (Eq. 11+12).

    ``sem_ids`` indexes ``h_sem`` independently of ``ids`` — pass the cache
    slots (``params["sem_slot"][ids]``) with the hot-set ``sem_cache`` buffer
    for the out-of-core layout (DESIGN.md §SemanticStore); defaults to
    ``ids`` for the full-resident table. ``rows`` selects the launch
    geometry (1 = scalar-prefetch row DMAs, >1 = blocked); ids are padded
    here (repeating row 0) to the row-block multiple and the pad rows are
    sliced off."""
    if interpret is None:
        interpret = not _on_tpu()
    n = ids.shape[0]
    d = h_str.shape[1]
    if rows is None:
        cfg = at.get_tuner().config_for(
            "gather_fuse",
            at.gather_fuse_bucket(n, d, h_sem.shape[1], wp.shape[1]),
            str(h_str.dtype), interpret)
        rows = cfg["rows"]
    rows_, np_ = at.row_block(n, rows, 1)
    ids_p = _pad_to(ids, 0, rows_)  # pad ids are 0 — valid rows, sliced off
    sem_p = None if sem_ids is None else _pad_to(sem_ids, 0, rows_)
    out = gather_fuse_pallas(ids_p, h_str, h_sem, wp, bp, wf, bf, sem_p,
                             rows=rows_, interpret=interpret)
    return out[:n]


def gather_fuse_params(params, ids, rows: int | None = None,
                       interpret: bool | None = None):
    """Drive the kernel straight from a model params dict, resolving the
    semantic layout the same way ``models/base.py::semantic_rows`` does."""
    if "sem_slot" in params:
        h_sem = params["sem_cache"]
        sem_ids = params["sem_slot"][ids]
    else:
        h_sem = params["sem_table"]
        sem_ids = None
    return gather_fuse(ids, params["entity"], h_sem, params["sem_proj_w"],
                       params["sem_proj_b"], params["fuse_w"],
                       params["fuse_b"], sem_ids=sem_ids, rows=rows,
                       interpret=interpret)


# Re-exported oracles (tests + fallback paths).
scoring_ref = ref.scoring_ref
intersect_ref = ref.intersect_ref
gather_fuse_ref = ref.gather_fuse_ref
