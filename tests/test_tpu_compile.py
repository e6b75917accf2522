"""The Pallas kernels compile for a TPU v5e chip at the published widths.

Interpret-mode tests cannot see what Mosaic refuses (unaligned blocks,
contractions it cannot lower), so each kernel of the main path is compiled
here for a described ``v5e:2x2`` topology, without a chip: gqe/complex_e
scoring at d=400, BetaE's intersection (state 800, hidden 800, k=2 and 3)
and the semantic gather-fuse at d=400, d_l=256, d_p=64 with the default
tiles. Nothing runs; the test asserts the compiled HLO holds the kernel.

The topology is described inside a fixture only: the TPU library may be
loaded by one process at a time, and the test workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, DL, DP, E, N = 400, 256, 64, 14505, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_text(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("mode", ["l1", "dot"])
def test_scoring_compiles_for_v5e(mode, one_chip, no_persistent_cache):
    hlo = _compile_text(
        lambda q, e: ops.scoring(q, e, gamma=12.0, mode=mode,
                                 interpret=False),
        [((64, D), F32), ((E, D), F32)], one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [2, 3])
def test_intersect_compiles_for_v5e(k, one_chip, no_persistent_cache):
    sd = 2 * D
    hlo = _compile_text(
        lambda x, w1, b1, w2, b2: ops.intersect(x, w1, b1, w2, b2,
                                                interpret=False),
        [((N, k, sd), F32), ((sd, sd), F32), ((sd,), F32), ((sd, 1), F32),
         ((1,), F32)], one_chip)
    assert "tpu_custom_call" in hlo


def test_gather_fuse_compiles_for_v5e(one_chip, no_persistent_cache):
    hlo = _compile_text(
        lambda *a: ops.gather_fuse(*a, interpret=False),
        [((N,), I32), ((E, D), F32), ((E, DL), F32), ((DL, DP), F32),
         ((DP,), F32), ((D + DP, D), F32), ((D,), F32)], one_chip)
    assert "tpu_custom_call" in hlo
