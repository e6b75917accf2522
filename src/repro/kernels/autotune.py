"""Kernel autotuner with a persisted tuning cache (DESIGN.md §Autotuner).

The Pallas kernels ship with hand-picked tile shapes (``scoring`` bm=128/
bn=256/bk=128, ``intersect`` bn=256, row-at-a-time ``gather_fuse``) — tuned
for exactly one shape regime. This module searches tile/block configurations
per **(op, shape-bucket, dtype, backend, interpret-mode)** and persists the
winner so the tuning cost is paid once per machine:

* **Shape buckets** — pool-rows dimensions are bucketed to the next power of
  two (the same ladder the scheduler's ``bucket_size`` pads to), feature
  dims are kept exact. One tuned config covers every pool that lands in the
  bucket, so the config set — like the jit signature set — stays closed.
* **Bit-identity verification** — every candidate's output is compared
  ``np.array_equal`` against the default-tile path (and float-checked
  against the ``kernels/ref.py`` oracle) on deterministic inputs BEFORE it
  is timed; a candidate that changes a single bit is rejected. Tile choice
  may only move work, never numerics.
* **Timed sweep** — median-of-iters wall time through the PUBLIC ``ops``
  wrappers (what actually runs), default config always among the
  candidates, so the tuned config is never slower than the default on the
  machine that tuned it (modulo timer noise).
* **Persisted cache** — crash-safe JSON (tmp + fsync + ``os.replace``, the
  ``SemanticStore`` idiom). A corrupt/partial/foreign-version file is
  REJECTED and retuned, never crashed on. ``REPRO_AUTOTUNE_CACHE`` names
  the default cache file for the process-wide tuner.

``PoolTilePolicy`` is the bridge to the compiler: it maps a scheduler pool
``(op, cardinality, rows)`` to the tuned row tile, and ``bucket_size`` pads
the pool to the smallest multiple of that tile instead of the bare power of
two — less pad waste AND kernel-aligned launches, with the policy's key
mixed into every schedule/plan cache key so the signature universe stays
closed (zero steady-state retraces).

Activity is published through the PR-7 ``MetricsRegistry`` (group
``autotune``): sweeps run, candidates timed, tuned-config lookups served vs
defaulted, rejected candidates, cache-file loads/saves.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.registry import get_registry

__all__ = [
    "LANE", "DEFAULTS", "KernelTuner", "PoolTilePolicy", "get_tuner",
    "set_tuner", "pow2ceil", "ceil_to", "rows_bucket", "row_block",
    "scoring_bucket", "intersect_bucket", "gather_fuse_bucket",
    "pool_tile_policy", "tune_for_model", "ENV_CACHE",
]

#: TPU lane width / MXU edge — the hardware alignment every feature-dim pad
#: in ``ops.py`` targets. Single-sourced here so the kernel wrappers and the
#: tuner's search spaces can never disagree about it.
LANE = 128

#: Hand-picked tiles the kernels shipped with — served whenever no tuned
#: entry exists, so an empty tuner is bit-and-trace-identical to the
#: pre-autotuner engine.
DEFAULTS: Dict[str, Dict[str, int]] = {
    "scoring": {"bm": 128, "bn": 256, "bk": 128},
    "intersect": {"bn": 256},
    "gather_fuse": {"rows": 1},
}

ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1


# --------------------------------------------------------------- shape math
def pow2ceil(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def ceil_to(n: int, m: int) -> int:
    """Smallest multiple of m >= n."""
    return -(-int(n) // int(m)) * int(m)


def rows_bucket(n: int, floor: int = 8) -> int:
    """Pow2 bucket for a rows-like dimension, floored at the minimum block."""
    return max(int(floor), pow2ceil(n))


def row_block(n: int, tile: int, floor: int = 8) -> Tuple[int, int]:
    """The ONE row-padding rule shared by the kernel wrappers and the
    compiler's kernel-aware ``bucket_size``: clamp the tuned ``tile`` to the
    pow2 bucket of ``n`` (a tile can never exceed the padded rows), then pad
    ``n`` to the smallest multiple of the clamped block. Returns
    ``(block, padded_n)`` with ``padded_n % block == 0``."""
    b = min(int(tile), rows_bucket(n, floor))
    return b, ceil_to(max(int(n), 1), b)


def scoring_bucket(B: int, N: int, d: int) -> Tuple[int, int, int]:
    return (rows_bucket(B), rows_bucket(N, LANE), int(d))


def intersect_bucket(n: int, k: int, d: int, hd: int) -> Tuple[int, ...]:
    return (rows_bucket(n), int(k), int(d), int(hd))


def gather_fuse_bucket(n: int, d: int, dl: int, dp: int) -> Tuple[int, ...]:
    return (rows_bucket(n, 1), int(d), int(dl), int(dp))


def _backend() -> str:
    import jax

    return jax.default_backend()


def cache_key(op: str, bucket: Sequence[int], dtype: str,
              interpret: bool) -> str:
    """Flat string key: op + shape bucket + dtype + backend + interpret mode
    (interpret-mode timings on a CPU host must never be mistaken for Mosaic
    timings on a TPU — they tune different cost models)."""
    shp = "x".join(str(int(v)) for v in bucket)
    mode = "interpret" if interpret else "compiled"
    return f"{op}|{shp}|{dtype}|{_backend()}|{mode}"


# ----------------------------------------------------------- search spaces
def scoring_candidates(bucket) -> List[Dict[str, int]]:
    Bb, Nb, _d = bucket
    out = [dict(DEFAULTS["scoring"])]
    # bk stays at one lane: splitting the k-loop differently reassociates the
    # fp32 accumulator and would fail the bit-identity gate by construction.
    for bm in (32, 64, 128, 256):
        for bn in (128, 256, 512):
            if bm <= rows_bucket(Bb) and bn <= rows_bucket(Nb, LANE):
                c = {"bm": bm, "bn": bn, "bk": 128}
                if c not in out:
                    out.append(c)
    return out


def intersect_candidates(bucket) -> List[Dict[str, int]]:
    nb = bucket[0]
    out = [dict(DEFAULTS["intersect"])]
    for bn in (8, 16, 32, 64, 128, 256, 512):
        if bn <= nb:
            c = {"bn": bn}
            if c not in out:
                out.append(c)
    return out


def gather_fuse_candidates(bucket) -> List[Dict[str, int]]:
    nb = bucket[0]
    out = [dict(DEFAULTS["gather_fuse"])]
    # Blocked tiles are whole f32 sublane groups: Mosaic refuses a (rows, d)
    # block whose rows is not a multiple of 8.
    for rows in (8, 16, 32, 64):
        if rows <= nb:
            c = {"rows": rows}
            if c not in out:
                out.append(c)
    return out


_CANDIDATES: Dict[str, Callable] = {
    "scoring": scoring_candidates,
    "intersect": intersect_candidates,
    "gather_fuse": gather_fuse_candidates,
}


# ------------------------------------------------------------------- tuner
@dataclasses.dataclass
class SweepResult:
    key: str
    config: Dict[str, int]
    us: float
    default_us: float
    n_candidates: int
    n_rejected: int


class KernelTuner:
    """Per-process tile tuner + the persisted on-disk tuning cache.

    Lookups (``config_for``) are a dict probe — safe on every hot path; the
    expensive sweep only runs when ``tune()`` / ``tune_for_model()`` is
    invoked explicitly (the bench, ``--autotune``, or a test). With no tuned
    entries the tuner serves ``DEFAULTS`` and the engine behaves exactly as
    before this subsystem existed."""

    def __init__(self, path: Optional[str] = None, iters: int = 3,
                 warmup: int = 1, max_candidates: int = 12,
                 margin: float = 0.10):
        if iters < 1 or warmup < 0 or max_candidates < 1:
            raise ValueError(
                f"iters >= 1, warmup >= 0, max_candidates >= 1 required; got "
                f"iters={iters} warmup={warmup} max_candidates={max_candidates}")
        if not 0.0 <= margin < 1.0:
            raise ValueError(f"margin must be in [0, 1); got {margin}")
        self.path = path
        self.iters = iters
        self.warmup = warmup
        self.max_candidates = max_candidates
        self.margin = margin
        self._entries: Dict[str, Dict] = {}
        self._lock = threading.RLock()
        self.load_error: Optional[str] = None
        m = get_registry().group("autotune")
        self._metrics = m
        self.sweeps = m.counter("sweeps")
        self.candidates_timed = m.counter("candidates_timed")
        self.lookup_hits = m.counter("lookup_hits")      # tuned config served
        self.lookup_misses = m.counter("lookup_misses")  # DEFAULTS served
        self.verify_rejects = m.counter("verify_rejects")
        self.loads = m.counter("loads")
        self.load_rejects = m.counter("load_rejects")
        self.saves = m.counter("saves")
        self.entries_gauge = m.gauge("entries")
        if path:
            self.load()

    # ------------------------------------------------------------- lookups
    def lookup(self, op: str, bucket, dtype: str = "float32",
               interpret: bool = True) -> Optional[Dict[str, int]]:
        with self._lock:
            e = self._entries.get(cache_key(op, bucket, dtype, interpret))
        return dict(e["config"]) if e else None

    def config_for(self, op: str, bucket, dtype: str = "float32",
                   interpret: bool = True) -> Dict[str, int]:
        """Tuned config for the bucket, or the hand-picked default."""
        c = self.lookup(op, bucket, dtype, interpret)
        if c is not None:
            self.lookup_hits += 1
            return c
        self.lookup_misses += 1
        return dict(DEFAULTS[op])

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __bool__(self) -> bool:
        # An empty tuner is still a tuner — never let ``len == 0`` make
        # ``tuner or get_tuner()``-style code swap in the global one.
        return True

    def entries(self) -> Dict[str, Dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def stats(self) -> Dict:
        with self._lock:
            n = len(self._entries)
        return {
            "name": "autotune",
            "path": self.path,
            "entries": n,
            "sweeps": int(self.sweeps),
            "candidates_timed": int(self.candidates_timed),
            "lookup_hits": int(self.lookup_hits),
            "lookup_misses": int(self.lookup_misses),
            "verify_rejects": int(self.verify_rejects),
            "loads": int(self.loads),
            "load_rejects": int(self.load_rejects),
            "saves": int(self.saves),
            "load_error": self.load_error,
        }

    def reset_counters(self) -> None:
        self._metrics.reset()

    # ------------------------------------------------------------ sweeping
    def tune(self, op: str, bucket, dtype: str = "float32",
             interpret: bool = True, force: bool = False) -> Dict[str, int]:
        """Ensure a tuned entry for the bucket (sweep once, then cached —
        in memory and, with a ``path``, on disk)."""
        if op not in _CANDIDATES:
            raise ValueError(f"unknown op {op!r}; tunable: {sorted(_CANDIDATES)}")
        key = cache_key(op, bucket, dtype, interpret)
        with self._lock:
            if not force and key in self._entries:
                return dict(self._entries[key]["config"])
        res = self._sweep(op, tuple(int(v) for v in bucket), dtype, interpret)
        with self._lock:
            self._entries[key] = {
                "op": op, "bucket": list(bucket), "dtype": dtype,
                "config": dict(res.config), "us": res.us,
                "default_us": res.default_us,
                "n_candidates": res.n_candidates,
                "n_rejected": res.n_rejected,
            }
            self.entries_gauge.set(len(self._entries))
        if self.path:
            self.save()
        return dict(res.config)

    def _sweep(self, op, bucket, dtype, interpret) -> SweepResult:
        self.sweeps += 1
        run, args = _make_runner(op, bucket, dtype, interpret)
        cands = _CANDIDATES[op](bucket)[: self.max_candidates]
        ref_out = np.asarray(run(cands[0], *args))  # default tiles = oracle
        best_cfg, best_us, default_us, rejected = dict(cands[0]), None, None, 0
        for cfg in cands:
            out = np.asarray(run(cfg, *args))
            if not np.array_equal(out, ref_out):
                # Tile choice may only move work, never numerics.
                self.verify_rejects += 1
                rejected += 1
                continue
            us = _time_us(lambda: run(cfg, *args), self.iters, self.warmup)
            self.candidates_timed += 1
            if default_us is None:
                # The default runs first; it is the incumbent to beat.
                default_us = us
                best_cfg, best_us = dict(cfg), us
            elif us < best_us and us < default_us * (1.0 - self.margin):
                # A challenger must beat the default by ``margin`` (not just
                # by a timer tick) — ties and noise-level wins stay with the
                # default, so "tuned never slower" is robust to host jitter.
                best_cfg, best_us = dict(cfg), us
        return SweepResult(
            key=cache_key(op, bucket, dtype, interpret), config=best_cfg,
            us=float(best_us), default_us=float(default_us),
            n_candidates=len(cands), n_rejected=rejected)

    # --------------------------------------------------------- persistence
    def save(self) -> None:
        """Crash-safe publish: tmp + fsync + atomic rename (the
        ``SemanticStore`` idiom) — a reader never sees partial bytes."""
        if not self.path:
            return
        with self._lock:
            payload = {"version": CACHE_VERSION, "entries": dict(self._entries)}
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
            self.saves += 1

    def load(self) -> int:
        """Load the persisted cache; a corrupt, partial, or foreign-version
        file is rejected whole (``load_error`` records why) and the tuner
        simply retunes — it must never crash the engine."""
        self.load_error = None
        if not self.path or not os.path.exists(self.path):
            return 0
        try:
            with open(self.path) as f:
                payload = json.load(f)
            if not isinstance(payload, dict):
                raise ValueError("cache root is not an object")
            if payload.get("version") != CACHE_VERSION:
                raise ValueError(
                    f"cache version {payload.get('version')!r} != "
                    f"{CACHE_VERSION}")
            raw = payload.get("entries")
            if not isinstance(raw, dict):
                raise ValueError("cache has no entries object")
            good: Dict[str, Dict] = {}
            for k, e in raw.items():
                if (isinstance(k, str) and isinstance(e, dict)
                        and isinstance(e.get("config"), dict)
                        and e.get("op") in DEFAULTS
                        and set(e["config"]) == set(DEFAULTS[e["op"]])
                        and all(isinstance(v, int) and v >= 1
                                for v in e["config"].values())):
                    good[k] = e
                else:
                    raise ValueError(f"malformed entry {k!r}")
        except (OSError, ValueError, json.JSONDecodeError) as err:
            self.load_error = f"{type(err).__name__}: {err}"
            self.load_rejects += 1
            return 0
        with self._lock:
            self._entries.update(good)
            self.entries_gauge.set(len(self._entries))
        self.loads += 1
        return len(good)


def _time_us(fn: Callable, iters: int, warmup: int) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    # Min, not mean/median: host timing noise is strictly additive, so the
    # fastest observation is the least-contaminated estimate.
    return min(ts) * 1e6


def _make_runner(op: str, bucket, dtype: str, interpret: bool):
    """Deterministic inputs at the bucket shape + a runner that drives the
    PUBLIC ``ops`` wrapper with an explicit candidate config — the sweep
    times exactly the code path production takes."""
    import jax.numpy as jnp

    from repro.kernels import ops  # function-level: ops imports this module

    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    if op == "scoring":
        B, N, d = bucket
        q = jnp.asarray(rng.normal(size=(B, d)), dt)
        e = jnp.asarray(rng.normal(size=(N, d)), dt)

        def run(cfg, q, e):
            return ops.scoring(q, e, gamma=1.0, mode="dot", bm=cfg["bm"],
                               bn=cfg["bn"], bk=cfg["bk"], interpret=interpret)

        return run, (q, e)
    if op == "intersect":
        n, k, d, hd = bucket
        x = jnp.asarray(rng.normal(size=(n, k, d)), dt)
        w1 = jnp.asarray(rng.normal(size=(d, hd)) * 0.2, jnp.float32)
        b1 = jnp.asarray(rng.normal(size=(hd,)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(hd, 1)) * 0.2, jnp.float32)
        b2 = jnp.zeros((1,), jnp.float32)

        def run(cfg, *a):
            return ops.intersect(*a, bn=cfg["bn"], interpret=interpret)

        return run, (x, w1, b1, w2, b2)
    if op == "gather_fuse":
        n, d, dl, dp = bucket
        E = max(n, 64)
        ids = jnp.asarray(rng.integers(0, E, n), jnp.int32)
        h_str = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
        h_sem = jnp.asarray(rng.normal(size=(E, dl)), jnp.float32)
        wp = jnp.asarray(rng.normal(size=(dl, dp)) * 0.2, jnp.float32)
        bp = jnp.asarray(rng.normal(size=(dp,)) * 0.1, jnp.float32)
        wf = jnp.asarray(rng.normal(size=(d + dp, d)) * 0.2, jnp.float32)
        bf = jnp.zeros((d,), jnp.float32)

        def run(cfg, *a):
            return ops.gather_fuse(*a, rows=cfg["rows"], interpret=interpret)

        return run, (ids, h_str, h_sem, wp, bp, wf, bf)
    raise ValueError(op)  # pragma: no cover


# ---------------------------------------------------------- process tuner
_GLOBAL: Optional[KernelTuner] = None
_GLOBAL_LOCK = threading.Lock()


def get_tuner() -> KernelTuner:
    """Process-wide tuner. Created lazily; picks up ``REPRO_AUTOTUNE_CACHE``
    as its persisted cache path when set (the ``run.sh`` launcher sets it)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = KernelTuner(path=os.environ.get(ENV_CACHE) or None)
        return _GLOBAL


def set_tuner(tuner: Optional[KernelTuner]) -> Optional[KernelTuner]:
    """Install (or with ``None`` reset) the process-wide tuner; returns the
    previous one so tests can restore it."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        prev, _GLOBAL = _GLOBAL, tuner
        return prev


# ------------------------------------------------------- compiler bridge
class PoolTilePolicy:
    """Maps a scheduler pool ``(op, cardinality, rows)`` to the tuned row
    tile its padded size must be a multiple of (``scheduler.bucket_size``
    consumes it). ``key()`` enters every schedule/plan cache key, so two
    executors holding different tunings can never share a schedule — the
    signature universe stays closed per policy."""

    def __init__(self, tiles: Dict[Tuple[int, int, int], int]):
        for (op, card, bucket), t in tiles.items():
            if t < 1 or (t & (t - 1)):
                raise ValueError(
                    f"tile for pool (op={op}, card={card}, bucket={bucket}) "
                    f"must be a power of two >= 1, got {t}")
        self._tiles = dict(tiles)
        self._key = tuple(sorted(self._tiles.items()))

    def tile(self, op: int, card: int, n: int) -> int:
        if not self._tiles:
            return 1
        return self._tiles.get((int(op), int(card), rows_bucket(n, 1)), 1)

    def key(self) -> Tuple:
        return self._key

    def __bool__(self) -> bool:
        return bool(self._tiles)

    def __repr__(self) -> str:
        return f"PoolTilePolicy({len(self._tiles)} tiles)"


def pool_tile_policy(model, tuner: Optional[KernelTuner] = None,
                     b_max: int = 512) -> Optional[PoolTilePolicy]:
    """Build the kernel-aware padding policy for ``model`` from whatever the
    tuner has learned. Tiles come from tuned entries whose feature dims
    match the model (intersect/union pools gate on ``state_dim``; embed
    pools on the fused-entity ``cfg.dim``); with no matching entries the
    result is ``None`` and the compiler keeps bare pow2 padding — the
    pre-autotuner engine, bit for bit."""
    from repro.core.ops import OpType

    tuner = get_tuner() if tuner is None else tuner
    tiles: Dict[Tuple[int, int, int], int] = {}
    try:
        sd = int(model.state_dim)
    except Exception:
        sd = -1
    dim = int(getattr(model.cfg, "dim", -1))
    for e in tuner.entries().values():
        bucket = e.get("bucket") or []
        cfg = e["config"]
        if e["op"] == "intersect" and len(bucket) == 4 and bucket[2] == sd:
            nb, k = int(bucket[0]), int(bucket[1])
            if nb <= rows_bucket(b_max, 1):
                t = int(cfg["bn"])
                for op in (OpType.INTERSECT, OpType.UNION):
                    tiles[(int(op), k, nb)] = t
        elif e["op"] == "gather_fuse" and len(bucket) == 4 and bucket[1] == dim:
            nb = int(bucket[0])
            if nb <= rows_bucket(b_max, 1):
                tiles[(int(OpType.EMBED), 0, nb)] = int(cfg["rows"])
    return PoolTilePolicy(tiles) if tiles else None


def tune_for_model(model, tuner: Optional[KernelTuner] = None,
                   b_max: int = 512, batch: int = 128,
                   n_entities: int = 4096, cards: Sequence[int] = (2, 3),
                   interpret: Optional[bool] = None) -> int:
    """Bounded sweep over the buckets one model/shape regime actually hits:
    scoring at (batch x entities x dim), intersect at the pool buckets the
    scheduler can form (up to ``b_max``) per cardinality class, gather_fuse
    at the embed working set. Returns the number of sweeps run (0 when the
    persisted cache already covers everything)."""
    import jax

    tuner = get_tuner() if tuner is None else tuner
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    before = int(tuner.sweeps)
    dim = int(model.cfg.dim)
    sd = int(model.state_dim)
    tuner.tune("scoring", scoring_bucket(batch, n_entities, dim),
               interpret=interpret)
    hd = None
    # Intersect MLP width from the model's own attention params when it
    # exposes one (BetaE: att_w0 [2d, h]); fall back to hidden_mult * dim.
    try:
        probe = model.init_geometry(jax.random.PRNGKey(0), 8, 4)
        for name in ("att_w0", "int_w0"):
            if name in probe:
                hd = int(probe[name].shape[1])
                break
    except Exception:
        pass
    if hd is None:
        hd = int(getattr(model.cfg, "hidden_mult", 2) * dim)
    # Full pow2 ladder up to the largest pool the scheduler can form, so the
    # tile policy has an answer for EVERY pool bucket (a bucket without an
    # entry falls back to pow2 padding — correct, just not kernel-aware).
    top = rows_bucket(min(4 * batch, b_max))
    pool_buckets = []
    nb = 8
    while nb <= top:
        pool_buckets.append(nb)
        nb *= 2
    for k in cards:
        for nb in pool_buckets:
            tuner.tune("intersect", intersect_bucket(nb, k, sd, hd),
                       interpret=interpret)
    if model.cfg.semantic_dim > 0:
        dl = int(model.cfg.semantic_dim)
        dp = int(model.cfg.semantic_proj_dim)
        tuner.tune("gather_fuse",
                   gather_fuse_bucket(min(4 * batch, b_max), dim, dl, dp),
                   interpret=interpret)
    return int(tuner.sweeps) - before
