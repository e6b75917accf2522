#!/usr/bin/env python3
"""Distinct compiled-program signatures against batches drawn, host only.

  python bench/tools/signature_curve.py --config gqe-fb237-d400 \
      --traffic train-online --batches 400 --seeds 1 2 3

The planner (``PooledExecutor.prepare``) runs on the host, so this needs no
accelerator: it builds the configuration's graph at full size, draws the
traffic mix's stream from each seed, prepares every batch and prints how
many distinct signatures have appeared after n batches of the cell's
training feed. The warm-up of a cell is sized from this curve; ``--cse``
overrides the configuration's CSE to show the curve it would give.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402


def signature_curve(cfg: Dict, mix: Dict, kg, seed: Optional[int],
                    n_batches: int, cse: Optional[bool] = None) -> List[int]:
    """Distinct program signatures after each of ``n_batches`` batches of
    the mix drawn from ``seed`` (None: the warm-up stream)."""
    from repro.core import PooledExecutor
    from repro.kernels import autotune
    from repro.models import ModelConfig, make_model

    autotune.set_tuner(autotune.KernelTuner(path=None))
    model = make_model(cfg["family"], ModelConfig(**cfg["model"]))
    seen, curve = set(), []
    t = cfg["trainer"]
    ex = PooledExecutor(model, b_max=t["b_max"],
                        cse=t["cse"] if cse is None else cse,
                        cache_size=100000)
    s = (mix["warmup_seed"] if seed is None
         else harness.derive_seed(seed, "train"))
    sampler = harness.online_sampler(kg, mix, s)
    feed = harness.train_feed(sampler, mix, t["batch_size"], s)
    for _ in range(n_batches):
        batch = feed()
        seen.add(ex.prepare([b.query for b in batch]).signature)
        curve.append(len(seen))
    return curve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--batches", type=int, default=400)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--cse", type=int, choices=(0, 1), default=None,
                    help="training: override the configuration's CSE")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    harness.add_src_path()
    cfg = harness.load_config(args.config)
    mix = harness.load_traffic(args.traffic)
    kg = harness.build_graph(cfg)
    marks = sorted({n for n in (1, 2, 4, 8, 16, 32, 64, 100, 150, 200, 300,
                                400, 600, 800, 1000, 1500, 2000)
                    if n <= args.batches} | {args.batches})
    for seed in [None] + list(args.seeds):
        curve = signature_curve(cfg, mix, kg, seed, args.batches,
                                cse=None if args.cse is None
                                else bool(args.cse))
        label = "warm-up stream" if seed is None else f"seed {seed}"
        print(f"{label}: " + ", ".join(f"{n}:{curve[n - 1]}" for n in marks),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
