"""A small stand-in of the mesh cell for CPU tests on 4 emulated devices.

``betae-wikikg2.train-fsdp4`` as ``tiny`` shrinks a cell (batch 56, 8
negatives, short warm-up), at dim 96 on an 8,192-entity graph: the smallest
round width at which the fsdp profile's 65,536-element floor shards every
matrix the full cell shards (entity table by rows, projection and attention
MLPs), with the table large enough beside the batch that, as at full size,
no collective of the step carries a whole table shard.

``run(argv)`` runs one process with 4 emulated devices (they exist only if
``XLA_FLAGS`` is set before JAX starts) and returns what it prints last.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "betae-wikikg2.train-fsdp4"
DIM, N_ENTITIES, N_TRAIN = 96, 8192, 30000


def config(name):
    c = tiny.config(name)
    c["model"]["dim"] = DIM
    c["graph"].update(n_entities=N_ENTITIES, n_train=N_TRAIN)
    return c


def install() -> None:
    """Shrink every cell this process loads."""
    from bench import harness

    harness.load_config = config
    harness.load_traffic = tiny.traffic
    harness.add_src_path()


# The head of a script run by ``run``: 4 CPU devices, the repository and
# this directory on sys.path, the cells shrunk.
PRELUDE = f"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{tiny.ROOT!r}, {HERE!r}]
import tiny_mesh
tiny_mesh.install()
"""


def run(body: str, timeout: float = 600) -> dict:
    """Run ``PRELUDE + body`` in a fresh process; ``body`` prints one JSON
    line last."""
    r = subprocess.run([sys.executable, "-c", PRELUDE + body],
                       capture_output=True, text=True, timeout=timeout,
                       cwd=tiny.ROOT)
    lines = r.stdout.strip().splitlines()
    assert r.returncode == 0 and lines, (r.stdout[-2000:], r.stderr[-4000:])
    return json.loads(lines[-1])
