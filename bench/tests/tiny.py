"""Small stand-ins of the cells for CPU tests: the same configurations and
mixes with dim 16, a 600-entity graph and short warm-ups."""
from __future__ import annotations

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

_load_config, _load_traffic = harness.load_config, harness.load_traffic


def config(name):
    c = copy.deepcopy(_load_config(name))
    c["model"]["dim"] = 16
    c["graph"].update(n_entities=600, n_relations=20, n_train=5000,
                      n_valid=100, n_test=100)
    c["trainer"].update(batch_size=56, n_negatives=8, b_max=64)
    return c


def traffic(name):
    m = copy.deepcopy(_load_traffic(name))
    m["warmup_batches"] = 2
    return m


def shrink(monkeypatch):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(harness, "load_config", config)
    monkeypatch.setattr(harness, "load_traffic", traffic)
    harness.add_src_path()


def run_cell(monkeypatch, capsys, workload, seed=4294967311, seconds=2):
    """One run of ``workload`` at the small size, past the look for a chip;
    returns the parsed result line."""
    shrink(monkeypatch)
    from bench import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-3:]
    return json.loads(out[-1])
