#!/usr/bin/env python3
"""``control.py`` for a cell whose reference follows the touched entity rows
(``bench/reference_rows.py``): the control and the planted faults at the
cell's own size, read against the row-restricted float32 reference.

  python3 bench/tools/control_rows.py --workload betae-wikikg2.train-fsdp4 \
      --seeds 11 12 13

The weights are made sharded over the cell's mesh where the host has its
chips, else whole on the first chip (a 2,500,604 x 400 table is 4.0 GB).
Prints one line per seed and reading, as ``control.py`` does, each with
the verdict of the cell's own limits (``checks.judge``) on it.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import checks, harness, reference, reference_rows  # noqa: E402
from bench.tools import control  # noqa: E402


def train_control(cfg, mix, kg, seed: int, init):
    inputs = control.train_inputs(cfg, mix, kg, seed, mix["checked_steps"])
    weights = init(cfg, seed)        # made once for the three readings
    read = functools.partial(reference_rows.reference_readings, cfg, seed,
                             inputs, lambda c, s: weights)
    ref = read()
    harness.log(f"control: seed {seed}: {ref['rows']} entity rows, "
                f"reference losses {ref['losses']}")
    out = {"control_bf16": checks.compare_train(read(precision="bfloat16"),
                                                ref),
           "half_batch": checks.compare_train(read(keep=0.5), ref)}
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
    init = reference.init_params
    if len(jax.devices()) >= cell["chips"] > 1:
        from bench.kinds import train_mesh
        from repro.distributed.context import make_execution_context

        t = cfg["trainer"]
        ctx = make_execution_context(t["mesh"], profile=t["profile"])
        init = functools.partial(train_mesh.sharded_init, ctx)
    t0 = time.perf_counter()
    kg = harness.build_graph(cfg)
    harness.log(f"control: graph {time.perf_counter() - t0:.1f}s")
    for seed in args.seeds:
        out = train_control(cfg, mix, kg, seed, init)
        for name, readings in out.items():
            ok = checks.judge(cell, checks._with_limits(cell["name"],
                                                        readings))
            print(f"control: {args.workload} seed {seed} {name}: "
                  + " ".join(f"{k}={v!r}" for k, v in readings.items())
                  + f" correct={ok}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
