#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix's ``kind`` picks the runner
(``bench/kinds/<kind>.py``). One process holds the chip: it loads, warms
up every shape the window uses, measures for ``--seconds``, checks what the
timed path produced against the plain reference (``bench/reference.py``),
and prints one JSON line last on stdout::

  {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
   "device": {...}, ["breakdown": {...},] "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from the program's spans and counters and the profiler's
device trace. Set-up phases, compile seconds and persistent-cache hits go
to stderr before it. A host without a TPU, or with fewer chips than the
cell asks for, exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's trace of a --trace 1 run here")
    return ap.parse_args(argv)


def main(argv=None, *, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        bench = harness.load_benchmark()
        cell = harness.find_cell(bench, args.workload)
        cfg = harness.load_config(cell["config"])
        mix = harness.load_traffic(cell["traffic"])
        harness.add_src_path()
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    from repro.kernels import autotune
    from repro.xla_cache import enable_persistent_cache

    # Persist every program, however fast it compiled, in the checkout's
    # fixed cache directory (or JAX_COMPILATION_CACHE_DIR), so the second
    # run of a cell loads all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache_dir = enable_persistent_cache()
    # Shipped kernel tiles only, never a tuned-tile file of this checkout.
    autotune.set_tuner(autotune.KernelTuner(path=None))
    harness.log(f"bench: {args.workload} seed {args.seed} on "
                f"{devices[0].device_kind} x{len(devices)}, jax "
                f"{jax.__version__}, compile cache {cache_dir}")
    run = harness.Run(args=args, bench=bench, cell=cell, cfg=cfg, mix=mix,
                      devices=devices[:cell["chips"]], t_start=T_START)
    kind = harness.load_module("kinds", mix["kind"])
    try:
        result = kind.run(run)
    except Exception:
        traceback.print_exc()
        print("bench: the run failed", file=sys.stderr)
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
