"""Fig 7 / Table 2: multi-device scaling, now through the ExecutionContext.

The container has one physical CPU core, so wall-clock multi-device speedup
is not measurable; what IS measurable — and what this sweep asserts — is the
paper's scaling *invariants* on emulated host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``). The sweep runs in
a child process that is CPU-only: its own environment sets
``JAX_PLATFORMS=cpu``, so it never asks for a chip the parent may hold. The
same invariants on four real chips are checked by
``python chip_smoke.py --four-chips``.

* **correctness** — pipelined sharded training (mesh ``data=N``, fsdp
  profile) reproduces the single-device sync per-step losses within float
  tolerance on the SAME replayed batches, for every device count;
* **memory** — the entity table's per-device bytes are exactly 1/N of the
  logical table (the fsdp profile shards its row dim over the data axis;
  ``entity_pad`` keeps the rows divisible);
* **compile stability** — after one pass over the batch signatures, the
  train-step compile cache hit rate is 100%: ZERO steady-state retraces on
  any mesh shape.

The summary (per-device param/entity bytes, steps/s, retrace counts) lands
in ``BENCH_scaling.json`` at the repo root so the perf trajectory
accumulates across PRs; violated invariants raise, so CI fails loudly when
invoked directly (``benchmarks/run.py`` prints an ERROR CSV row for it and
exits non-zero).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_DEFAULT_OUT = os.path.join(_REPO_ROOT, "BENCH_scaling.json")

DEVICE_COUNTS = (1, 2, 4, 8)

# __DEVICE_COUNTS__ / __MAX_DEVICES__ are substituted below so the sweep,
# the emulated-device count and run()'s assertions share one source of truth.
_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=__MAX_DEVICES__"
import sys, json, time
sys.path.insert(0, "src")
import jax, numpy as np
from repro.data import generate_synthetic_kg
from repro.distributed.context import ExecutionContext, make_execution_context
from repro.models import ModelConfig, make_model
from repro.sampling import OnlineSampler
from repro.training import AdamConfig, NGDBTrainer, TrainConfig

E, R, DIM, B, NEG = 4096, 12, 32, 32, 8
WARMUP, MEASURE = 4, 12
STEPS = WARMUP + MEASURE
kg = generate_synthetic_kg(E, R, 16000, seed=0)
sampler = OnlineSampler(kg, seed=7)
batches = [sampler.sample_batch(B) for _ in range(4)]  # fixed replay workload

# Unique train-step signatures of the replay workload (host-side probe): a
# run with ZERO steady-state retraces traces exactly this many programs.
from repro.core import PooledExecutor
probe_model = make_model("gqe", ModelConfig(dim=DIM, entity_pad=8))
probe = PooledExecutor(probe_model, b_max=512)
N_SIGS = len({probe.prepare([q.query for q in b]).signature for b in batches})

def make_trainer(ctx, pipeline):
    model = make_model("gqe", ModelConfig(dim=DIM, entity_pad=8))
    cfg = TrainConfig(batch_size=B, n_negatives=NEG, adam=AdamConfig(lr=1e-3),
                      pipeline=pipeline, seed=0)
    return NGDBTrainer(model, kg, cfg, ctx=ctx)

def run_all(tr):
    # ONE train() call per trainer: the negative-sampling RNG draws then
    # happen in deterministic item order for sync and pipelined alike (a
    # second call would see RNG state advanced by however far the first
    # call had prefetched ahead). Measured-window throughput comes from the
    # per-step records, so warmup compiles are excluded.
    tr.train(STEPS, log_every=0, batches=batches)
    jax.block_until_ready(tr.params)
    dur = sum(B / r["queries_per_sec"] for r in tr.history[WARMUP:])
    return MEASURE / dur, [r["loss"] for r in tr.history]

def per_device_bytes(params):
    ent = params["entity"]
    total = sum(p.nbytes for p in jax.tree.leaves(params))
    per_dev = sum(p.addressable_shards[0].data.nbytes
                  for p in jax.tree.leaves(params))
    return {"entity_bytes_total": int(ent.nbytes),
            "entity_bytes_per_device": int(ent.addressable_shards[0].data.nbytes),
            "param_bytes_total": int(total),
            "param_bytes_per_device": int(per_dev)}

# Baseline: single-device sync — the loss reference for every mesh shape.
base_sps, base_losses = run_all(
    make_trainer(ExecutionContext.single_device(), pipeline=False))

out = {"config": {"entities": E, "dim": DIM, "batch": B, "negatives": NEG,
                  "warmup_steps": WARMUP, "measure_steps": MEASURE,
                  "unique_signatures": N_SIGS,
                  "profile": "fsdp", "pipeline": True},
       "single_device_sync": {"steps_per_s": base_sps,
                              "losses": base_losses},
       "devices": {}}

for n in __DEVICE_COUNTS__:
    ctx = make_execution_context(f"data={n}", profile="fsdp")
    tr = make_trainer(ctx, pipeline=True)
    sps, tr_losses = run_all(tr)
    cc = tr.compile_cache_stats()["train_step"]
    # Every signature appears within the first replay cycle (= warmup), so
    # any trace beyond N_SIGS is a steady-state retrace.
    retraces = int(cc["misses"]) - N_SIGS
    rec = per_device_bytes(tr.params)
    rec.update({
        "steps_per_s": sps,
        "warmup_traces": N_SIGS,
        "steady_retraces": retraces,
        "steady_hit_rate": 1.0 if retraces == 0 else
            1.0 - retraces / max(STEPS - N_SIGS, 1),
        "loss_max_abs_diff_vs_single": float(np.abs(
            np.array(tr_losses) - np.array(base_losses)).max()),
        "entity_sharding": str(tr.params["entity"].sharding.spec),
    })
    out["devices"][str(n)] = rec

print("RESULT " + json.dumps(out))
"""


def run(out_path: str = _DEFAULT_OUT) -> dict:
    script = (_SCRIPT
              .replace("__DEVICE_COUNTS__", repr(tuple(DEVICE_COUNTS)))
              .replace("__MAX_DEVICES__", str(max(DEVICE_COUNTS))))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=1800, cwd=_REPO_ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    try:
        data = json.loads(lines[0][len("RESULT "):]) if lines else None
    except json.JSONDecodeError:
        data = None
    if data is None:
        # Publish the failed verdict BEFORE raising: a stale ok=true file
        # from a previous good run must not satisfy CI's json check when the
        # sweep itself never produced a result.
        with open(out_path, "w") as f:
            json.dump({"ok": False,
                       "failures": ["sweep subprocess produced no RESULT"],
                       "stderr_tail": r.stderr[-2000:]}, f, indent=1)
        emit("scaling/error", 0.0,
             r.stderr[-300:].replace(",", ";").replace("\n", " "))
        raise RuntimeError(f"scaling sweep produced no RESULT: {r.stderr[-2000:]}")

    failures = []
    for n in map(str, DEVICE_COUNTS):
        d = data["devices"][n]
        # Acceptance invariants (ISSUE 3): parity, 1/N memory, zero retraces.
        if d["loss_max_abs_diff_vs_single"] > 2e-3:
            failures.append(f"{n}dev loss diverges from single-device sync "
                            f"by {d['loss_max_abs_diff_vs_single']:.2e}")
        if d["entity_bytes_per_device"] * int(n) != d["entity_bytes_total"]:
            failures.append(
                f"{n}dev entity bytes/device {d['entity_bytes_per_device']} "
                f"!= 1/{n} of {d['entity_bytes_total']}")
        if d["steady_retraces"] != 0 or d["steady_hit_rate"] < 1.0:
            failures.append(f"{n}dev retraced after warmup "
                            f"({d['steady_retraces']} traces, hit rate "
                            f"{d['steady_hit_rate']:.2%})")
        emit(f"scaling/{n}dev_steps_per_s", 0.0, f"{d['steps_per_s']:.2f}")
        emit(f"scaling/{n}dev_entity_bytes_per_dev", 0.0,
             f"{d['entity_bytes_per_device']}")
        emit(f"scaling/{n}dev_param_bytes_per_dev", 0.0,
             f"{d['param_bytes_per_device']}")
        emit(f"scaling/{n}dev_steady_retraces", 0.0, f"{d['steady_retraces']}")
        emit(f"scaling/{n}dev_loss_max_abs_diff", 0.0,
             f"{d['loss_max_abs_diff_vs_single']:.2e}")

    data["ok"] = not failures
    data["failures"] = failures
    with open(out_path, "w") as f:
        json.dump(data, f, indent=1)
    emit("scaling/summary_json", 0.0, os.path.relpath(out_path, _REPO_ROOT))
    assert not failures, "; ".join(failures)
    return data


if __name__ == "__main__":
    run()
