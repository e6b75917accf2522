#!/usr/bin/env python3
"""Bring-up smoke run of the main path on a TPU chip.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # fsdp on a 2x2 host vs one chip

One chip: trains gqe at dim 400 on the Table 4 FB15k-237 graph through
``repro.launch.train.main`` (sync, then pipelined; each run ends with the
built-in eval, which scores all 14,505 entities), serves a mixed-pattern
workload from the checkpoint the pipelined run wrote through
``repro.launch.serve.main``, and checks each path against a plain reference
that does not go through the code under test:

* pooled encode and the fused first-step loss vs the query-level executor
  and a plain ``jnp`` loss, on the same batch and params;
* the served top-k scores vs a plain ``jnp`` ``gamma - |q - e|_1`` over
  every entity;
* each Pallas kernel, compiled (``interpret=False``), at real widths vs
  ``repro.kernels.ref``.

``--four-chips`` runs only this: a few fsdp training steps on a ``data=4``
mesh on replayed batches, compared in loss with the same batches on one
chip, plus the entity table's bytes per device (one quarter of the table).

Everything runs in this one process. Graph, params and batches come from
``--seed``. Lines starting ``smoke:`` are bring-up readings of one run, not
benchmark numbers. The last stdout line is ``{"ok": true, "device": ...}``;
a failed check, an exception or a host without a TPU exits non-zero
without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# ----------------------------------------------------------------- tolerances
# TPU f32 matmuls run at the default precision, which rounds each input to
# bf16 (8-bit mantissa, relative step 2**-8). Kernel and model outputs are
# compared with an exact-f32 reference (precision "highest").
#
# Kernels with a matmul must be no further from the exact answer than twice
# XLA's own default-precision result for the same call, and never need to be
# closer than one bf16 step of the output's scale: the kernel may multiply
# in bf16 exactly like XLA does.
MATMUL_FLOOR_REL = 2.0 ** -8
# A matmul-free kernel (l1 scoring) differs from the reference only by the
# order of its f32 sums over 400 terms: worst case d * 2**-24 = 2.4e-5 of
# the sum's scale; 2**-14 = 6.1e-5 leaves 2.5x headroom.
F32_SUM_REL = 2.0 ** -14
# Pooled vs query-level encode share the same operators but not the same
# pool shapes, so XLA may fuse and round the bf16 matmul inputs differently.
# One bf16 step per matmul, up to 3 chained matmuls (gqe's negation MLP
# feeding the intersection MLP): 3 * 2**-8 = 1.2e-2 of a row's norm.
ENCODE_REL = 3 * 2.0 ** -8
# The loss is a mean of 512 log-sigmoids of 65 scores each; encode rounding
# that survives the L1 sum and the mean is well under a bf16 step.
LOSS_REL = 2.0 ** -8
# Served scores are rounded to 3 decimals (engine contract), and the served
# query rows may differ from the query-level ones by ENCODE_REL of their
# norm, which moves an L1 score by at most that much of |q|_1.
SERVE_ROUND = 5e-4
# Loss parity, data=4 fsdp vs one chip on the same replayed batches: the
# sharded step sums the gradient over devices in another order; relative
# loss drift after a few Adam steps stays far below a bf16 step.
PARITY_REL = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The NGDB cell of ``launch/dryrun.py``: gqe at the published width
    (dim 400, Table 5) on the Table 4 FB15k-237 graph, batch 512, 64
    negatives. ``steps`` covers compile warm-up plus a few steady steps."""

    dataset: str = "FB15k-237"
    full_scale: bool = True
    model: str = "gqe"
    dim: int = 400
    batch: int = 512
    negatives: int = 64
    steps: int = 12
    eval_queries: int = 64
    requests: int = 48
    top_k: int = 10
    seed: int = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def _fail(msg: str):
    raise AssertionError(msg)


class CompileClock:
    """Sums XLA backend compile seconds and persistent-cache hits from
    JAX's monitoring events (process-wide; read as deltas)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.compile_s, self.compiles, self.cache_hits)


# -------------------------------------------------------------------- kernels
def check_kernels(seed: int, dim: int = 400, n_entities: int = 14505) -> None:
    """Each Pallas kernel compiled for the chip at real widths vs ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    def compare(name, run, oracle, args, floor_rel, matmul):
        compiled = jax.jit(run).lower(*args).compile()
        custom = "tpu_custom_call" in compiled.as_text()
        got = np.asarray(compiled(*args))
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(jax.jit(oracle)(*args))
        xla = np.asarray(jax.jit(oracle)(*args))
        scale = float(np.abs(exact).max())
        err = float(np.abs(got - exact).max())
        err_xla = float(np.abs(xla - exact).max())
        tol = max(2 * err_xla, floor_rel * scale) if matmul \
            else floor_rel * scale
        log(f"smoke: kernel {name}: tpu_custom_call={custom} "
            f"max|kernel-exact|={err:.3e} max|xla-exact|={err_xla:.3e} "
            f"tol={tol:.3e}")
        if not custom:
            _fail(f"kernel {name}: no tpu_custom_call in the compiled HLO")
        if not np.isfinite(got).all() or err > tol:
            _fail(f"kernel {name}: max error {err:.3e} > tol {tol:.3e}")

    # scoring: 64 queries against every entity of the graph.
    q, e = arr(64, dim, scale=dim ** -0.5), arr(n_entities, dim,
                                                  scale=dim ** -0.5)
    for mode, floor in (("l1", F32_SUM_REL), ("dot", MATMUL_FLOOR_REL)):
        compare(f"scoring[{mode}] d={dim}",
                lambda q, e, m=mode: ops.scoring(q, e, gamma=12.0, mode=m,
                                                 interpret=False),
                lambda q, e, m=mode: ref.scoring_ref(q, e, 12.0, m),
                (q, e), floor, matmul=(mode == "dot"))

    # intersect at BetaE widths: state 2*dim, attention hidden 2*dim.
    sd = 2 * dim
    w1, b1 = arr(sd, sd, scale=(2 / (2 * sd)) ** 0.5), arr(sd, scale=0.1)
    w2, b2 = arr(sd, 1, scale=(2 / (sd + 1)) ** 0.5), arr(1, scale=0.1)
    for k in (2, 3):
        x = arr(512, k, sd)
        compare(f"intersect k={k} state={sd} hidden={sd}",
                lambda x, w1, b1, w2, b2: ops.intersect(
                    x, w1, b1, w2, b2, interpret=False),
                ref.intersect_ref, (x, w1, b1, w2, b2), MATMUL_FLOOR_REL,
                matmul=True)

    # gather_fuse at the default rows: d=dim, d_l=256, d_p=64. rows=1 DMAs
    # the aligned 8-row tile holding each id; n_entities % 8 == 1, so the
    # last tile runs past the table end. Both index streams always hold the
    # first and last row of the first two tiles and the table's last row.
    dl, dp = 256, 64
    edge = np.array([0, 7, 8, n_entities - 1])
    ids, sem_ids = (jnp.asarray(np.concatenate(
        [edge, rng.integers(0, n_entities, 508)])[rng.permutation(512)],
        jnp.int32) for _ in range(2))
    h_str, h_sem = arr(n_entities, dim, scale=dim ** -0.5), arr(n_entities, dl)
    wp, bp = arr(dl, dp, scale=(2 / (dl + dp)) ** 0.5), arr(dp, scale=0.1)
    wf = arr(dim + dp, dim, scale=(2 / (2 * dim + dp)) ** 0.5)
    bf = arr(dim, scale=0.1)
    compare(f"gather_fuse d={dim} dl={dl} dp={dp}",
            lambda ids, sem_ids, h_str, h_sem, *w: ops.gather_fuse(
                ids, h_str, h_sem, *w, sem_ids=sem_ids, interpret=False),
            lambda ids, sem_ids, h_str, h_sem, *w: ref.gather_fuse_ref(
                jnp.arange(ids.shape[0]), h_str[ids], h_sem[sem_ids], *w),
            (ids, sem_ids, h_str, h_sem, wp, bp, wf, bf),
            MATMUL_FLOOR_REL, matmul=True)


# ------------------------------------------------------------------- training
def train_argv(cfg: SmokeConfig, run_dir: str, pipeline: bool):
    argv = ["--dataset", cfg.dataset, "--model", cfg.model,
            "--dim", str(cfg.dim), "--batch-size", str(cfg.batch),
            "--negatives", str(cfg.negatives), "--steps", str(cfg.steps),
            "--eval-queries", str(cfg.eval_queries), "--log-every", "1",
            "--ckpt-dir", os.path.join(run_dir, "ckpt"),
            "--metrics", os.path.join(run_dir, "metrics.jsonl"),
            "--seed", str(cfg.seed)]
    if cfg.full_scale:
        argv.append("--full-scale")
    if pipeline:
        argv.append("--pipeline")
    return argv


def run_training(cfg: SmokeConfig, run_dir: str, pipeline: bool, clock):
    import numpy as np

    from repro.launch import train

    mode = "pipelined" if pipeline else "sync"
    os.makedirs(run_dir, exist_ok=True)
    c0 = clock.snapshot() if clock else None
    t0 = time.perf_counter()
    out = train.main(train_argv(cfg, run_dir, pipeline))
    wall = time.perf_counter() - t0
    tr = out["trainer"]
    losses = [r["loss"] for r in tr.history]
    if len(losses) != cfg.steps or not np.isfinite(losses).all():
        _fail(f"{mode} training: losses {losses}")
    if out["mode"] != mode:
        _fail(f"asked for {mode} training, ran {out['mode']}")
    ev = out["eval"]
    if ev is None or not np.isfinite(ev["mrr"]) or ev["n"] <= 0:
        _fail(f"{mode} eval produced no ranks: {ev}")
    # Training throughput is not measured here: a dozen steps, most of them
    # compiling, give no rate. A sync step record times its own schedule +
    # dispatch + loss readback, without the wait for the sampler thread;
    # its mean over the steps that compiled nothing is reported as that. A
    # pipelined record spans the previous retire to its own, which gives
    # nothing to read over a handful of steps.
    timer = "not measured"
    if not pipeline:
        with open(os.path.join(run_dir, "metrics.jsonl")) as f:
            steady = [r for r in map(json.loads, f)
                      if r.get("kind") == "step" and "compile_s" not in r]
        if steady:
            ms = 1e3 * sum(cfg.batch / r["queries_per_sec"]
                           for r in steady) / len(steady)
            timer = (f"{ms:.3f} ms mean over {len(steady)} steps that "
                     f"compiled nothing")
    cc = tr.compile_cache_stats()["train_step"]
    line = (f"smoke: train[{mode}] {cfg.steps} steps in {wall:.1f}s wall, "
            f"{int(cc['misses'])} traced programs, steps/s not measured, "
            f"per-step dispatch + readback time (sampling excluded) "
            f"{timer}, eval mrr {ev['mrr']:.4f} over {int(ev['n'])} answers")
    if clock:
        c1 = clock.snapshot()
        line += (f", backend compile {c1[0] - c0[0]:.1f}s in "
                 f"{c1[1] - c0[1]} compiles, {c1[2] - c0[2]} cache hits")
    log(line)
    return out


def plain_l1_scores(gamma, q, ent):
    """gamma - |q - e|_1 against every row of ``ent``: [B, d] x [E, d]."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda q, e: gamma - jnp.sum(
        jnp.abs(q[:, None, :] - e[None, :, :]), axis=-1))(q, ent)


def check_encode_and_loss(cfg: SmokeConfig, train_out) -> None:
    """Pooled encode and the fused step's loss at the initial params vs the
    query-level executor and a plain jnp loss, on one seeded batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import PooledExecutor, QueryLevelExecutor
    from repro.sampling import OnlineSampler
    from repro.training.optim import adam_init

    tr = train_out["trainer"]
    model, kg = tr.model, tr.kg
    p0 = model.init_params(jax.random.PRNGKey(tr.cfg.seed), kg.n_entities,
                           kg.n_relations)
    sampler = OnlineSampler(kg, seed=cfg.seed + 1)
    batch = sampler.sample_batch(cfg.batch)
    queries, pos, neg = sampler.to_training_arrays(batch, cfg.negatives)

    pooled = np.asarray(tr.executor.encode(p0, queries, compiled=True))
    qlevel = np.asarray(QueryLevelExecutor(model).encode(p0, queries,
                                                         compiled=True))
    rel = (np.linalg.norm(pooled - qlevel, axis=1)
           / np.maximum(np.linalg.norm(qlevel, axis=1), 1e-30))
    log(f"smoke: encode pooled vs query-level: max row rel err "
        f"{rel.max():.3e} (tol {ENCODE_REL:.3e}) over {len(queries)} queries")
    if not np.isfinite(pooled).all() or rel.max() > ENCODE_REL:
        _fail(f"pooled encode differs from query-level by {rel.max():.3e}")

    # The fused train step the loop dispatches, at p0 on this batch.
    prepared = tr.executor.prepare(queries)
    steps, ans = prepared.device_args()
    fn = tr._train_fn(prepared)
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731 (donated args)
    _, _, loss, _ = fn(copy(p0), adam_init(copy(p0), tr.cfg.adam), steps,
                       ans, pos[prepared.order], neg[prepared.order])
    loss = float(loss)
    cand = jnp.concatenate([jnp.asarray(pos)[:, None], jnp.asarray(neg)], 1)
    ent = p0["entity"][cand]                                  # [B, 1+K, d]
    s = model.cfg.gamma - jnp.sum(
        jnp.abs(jnp.asarray(qlevel)[:, None, :] - ent), axis=-1)
    per = -jax.nn.log_sigmoid(s[:, 0]) - jnp.mean(
        jax.nn.log_sigmoid(-s[:, 1:]), axis=1)
    want = float(jnp.mean(per))
    log(f"smoke: first-step loss fused {loss:.6f} vs plain {want:.6f} "
        f"(rel {abs(loss - want) / abs(want):.3e}, tol {LOSS_REL:.3e})")
    if not np.isfinite(loss) or abs(loss - want) > LOSS_REL * abs(want):
        _fail(f"fused first-step loss {loss} vs plain {want}")

    # CSE on vs off, bitwise on this chip: an input to the bitwise
    # contracts, reported and not checked.
    off = np.asarray(PooledExecutor(model, b_max=tr.cfg.b_max, cse=False)
                     .encode(p0, queries, compiled=True))
    log(f"smoke: CSE on vs off encode bitwise equal on chip: "
        f"{bool(np.array_equal(pooled, off))} "
        f"(max abs diff {float(np.abs(pooled - off).max()):.3e})")


# -------------------------------------------------------------------- serving
def run_serving(cfg: SmokeConfig, ckpt_dir: str, trained_steps: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import QueryLevelExecutor
    from repro.launch import serve

    argv = ["--dataset", cfg.dataset, "--model", cfg.model,
            "--dim", str(cfg.dim), "--ckpt-dir", ckpt_dir,
            "--requests", str(cfg.requests), "--top-k", str(cfg.top_k),
            "--seed", str(cfg.seed)]
    if cfg.full_scale:
        argv.append("--full-scale")
    out = serve.main(argv)
    if out["restored_step"] != trained_steps:
        _fail(f"serving loaded step {out['restored_step']}, "
              f"want the trained step {trained_steps}")
    report, model, params = out["report"], out["model"], out["params"]
    queries = out["workload"]
    n = model.n_entities
    q = QueryLevelExecutor(model).encode(params, queries, compiled=True)
    ref = np.asarray(plain_l1_scores(model.cfg.gamma, q,
                                     jnp.asarray(params["entity"])[:n]))
    qnorm = np.abs(np.asarray(q)).sum(axis=1)
    worst = 0.0
    for i, res in enumerate(report.results):
        ids = np.asarray(res["top_entities"])
        got = np.asarray(res["scores"])
        tol = SERVE_ROUND + ENCODE_REL * qnorm[i]
        err = float(np.abs(got - ref[i, ids]).max())
        kth = np.sort(ref[i])[-len(ids)]
        worst = max(worst, err / tol)
        if len(ids) != min(cfg.top_k, n) or err > tol:
            _fail(f"request {i} ({res['pattern']}): served scores off the "
                  f"plain reference by {err:.3e} (tol {tol:.3e})")
        if ref[i, ids].min() < kth - 2 * tol:
            _fail(f"request {i} ({res['pattern']}): served top-{len(ids)} "
                  f"misses an entity scoring {kth:.4f}")
    log(f"smoke: serve {len(report.results)} requests at "
        f"{report.qps:.2f} requests/s (closed loop), p50 "
        f"{report.latency_ms['p50']:.1f} ms, p99 "
        f"{report.latency_ms['p99']:.1f} ms; top-{cfg.top_k} vs plain jnp "
        f"max err/tol {worst:.3f}; {out['stats']['retraces']} steady-state "
        f"retraces")


# ----------------------------------------------------------------- four chips
def run_four_chips(cfg: SmokeConfig) -> None:
    """fsdp on data=4 vs one chip on the same replayed batches."""
    import jax
    import numpy as np

    from repro.data import load_dataset
    from repro.distributed.context import (ExecutionContext,
                                           make_execution_context)
    from repro.models import ModelConfig, make_model
    from repro.sampling import OnlineSampler
    from repro.training import AdamConfig, NGDBTrainer, TrainConfig

    n_dev = len(jax.devices())
    if n_dev != 4:
        _fail(f"--four-chips needs 4 devices, found {n_dev}")
    kg, _, _ = load_dataset(cfg.dataset, reduced=not cfg.full_scale,
                            seed=cfg.seed)
    sampler = OnlineSampler(kg, seed=cfg.seed + 7)
    batches = [sampler.sample_batch(cfg.batch) for _ in range(2)]

    def run(ctx, pipeline):
        model = make_model(cfg.model, ModelConfig(dim=cfg.dim, gamma=12.0,
                                                  entity_pad=4))
        tcfg = TrainConfig(batch_size=cfg.batch, n_negatives=cfg.negatives,
                           adam=AdamConfig(lr=1e-3), pipeline=pipeline,
                           seed=cfg.seed)
        tr = NGDBTrainer(model, kg, tcfg, ctx=ctx)
        t0 = time.perf_counter()
        tr.train(cfg.steps, log_every=0, batches=batches)
        jax.block_until_ready(tr.params)
        return tr, [r["loss"] for r in tr.history], time.perf_counter() - t0

    _, base, t_one = run(ExecutionContext.single_device(), pipeline=False)
    ctx = make_execution_context("data=4", profile="fsdp")
    tr, sharded, t_four = run(ctx, pipeline=True)
    rel = max(abs(a - b) / abs(b) for a, b in zip(sharded, base))
    ent = tr.params["entity"]
    per_dev = ent.addressable_shards[0].data.nbytes
    log(f"smoke: one chip sync losses {[round(x, 6) for x in base]}")
    log(f"smoke: data=4 fsdp pipelined losses "
        f"{[round(x, 6) for x in sharded]}")
    log(f"smoke: loss parity max rel diff {rel:.3e} (tol {PARITY_REL:.3e}); "
        f"entity table {ent.nbytes} B logical, {per_dev} B/device "
        f"({ent.sharding.spec}); wall {t_one:.1f}s one chip, "
        f"{t_four:.1f}s data=4 ({cfg.steps} steps incl. compile)")
    if not np.isfinite(sharded).all() or rel > PARITY_REL:
        _fail(f"data=4 fsdp losses diverge from one chip by {rel:.3e}")
    if per_dev * 4 != ent.nbytes:
        _fail(f"entity bytes/device {per_dev} is not 1/4 of {ent.nbytes}")


def run_one_chip(cfg: SmokeConfig, clock=None, kernels: bool = True) -> None:
    import jax

    if kernels:
        t0 = time.perf_counter()
        check_kernels(cfg.seed, dim=cfg.dim)
        log(f"smoke: kernel checks {time.perf_counter() - t0:.1f}s")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        run_training(cfg, os.path.join(tmp, "sync"), False, clock)
        out = run_training(cfg, os.path.join(tmp, "pipelined"), True, clock)
        check_encode_and_loss(cfg, out)
        run_serving(cfg, os.path.join(tmp, "pipelined", "ckpt"), cfg.steps)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"smoke: peak_bytes_in_use "
        f"{peak if peak is not None else 'not reported'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=4 fsdp parity phase (2x2 host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.kernels import autotune
    from repro.xla_cache import enable_persistent_cache

    # Shipped tile defaults only: never a tuned-tile file of this checkout
    # (REPRO_AUTOTUNE_CACHE), so the run depends on committed files alone.
    autotune.set_tuner(autotune.KernelTuner(path=None))
    log(f"smoke: device {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {enable_persistent_cache()}")
    cfg = SmokeConfig(seed=args.seed)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            run_four_chips(cfg)
        else:
            run_one_chip(cfg, clock)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    log(f"smoke: total {time.perf_counter() - t0:.1f}s, backend compile "
        f"{clock.compile_s:.1f}s in {clock.compiles} compiles, "
        f"{clock.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
