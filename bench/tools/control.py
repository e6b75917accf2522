#!/usr/bin/env python3
"""Readings of the control and of the planted faults at a cell's own size.

  python3 bench/tools/control.py --workload gqe-fb237.train-online \
      --seeds 11 12 13

The control is the plain reference computed in bfloat16 (params and
arithmetic), put in the program's place and compared with the float32
reference exactly as a run compares the program. The planted faults are
read the same way: half of each batch left out with the mean taken over
the rest (``keep`` 0.5), and a step that leaves its state unchanged. Inputs
are the cell's training batches at its sizes, from the program's online
sampler on the seed's stream. Prints one line per seed and reading; the
limits in ``bench/limits`` are set from these lines and from the
program's own readings.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import checks, harness, reference  # noqa: E402


def train_inputs(cfg, mix, kg, seed: int, n_steps: int):
    t = cfg["trainer"]
    s = harness.derive_seed(seed, "train")
    feed = harness.train_feed(harness.online_sampler(kg, mix, s), mix,
                              t["batch_size"], s)
    neg = harness.online_sampler(kg, mix,
                                 harness.derive_seed(seed, "negatives"))
    return [neg.to_training_arrays(feed(), t["n_negatives"])
            for _ in range(n_steps)]


def train_control(cfg, mix, kg, seed: int):
    inputs = train_inputs(cfg, mix, kg, seed, mix["checked_steps"])
    ref = checks.reference_readings(cfg, seed, inputs)
    out = {"control_bf16": checks.compare_train(
        checks.reference_readings(cfg, seed, inputs, precision="bfloat16"),
        ref),
        "half_batch": checks.compare_train(
            checks.reference_readings(cfg, seed, inputs, keep=0.5), ref)}
    unchanged = dict(ref, change={k: 0.0 for k in ref["change"]})
    out["unchanged_state"] = checks.compare_train(unchanged, ref)
    return out


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(cell["config"])
    mix = harness.load_traffic(cell["traffic"])
    harness.add_src_path()
    import jax

    if require_tpu and jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    from repro.xla_cache import enable_persistent_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_persistent_cache()
    kg = harness.build_graph(cfg)
    for seed in args.seeds:
        out = train_control(cfg, mix, kg, seed)
        for name, readings in out.items():
            print(f"control: {args.workload} seed {seed} {name}: "
                  + " ".join(f"{k}={v!r}" for k, v in readings.items()),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
