"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import gather_fuse_ref, intersect_ref, scoring_ref


@pytest.mark.parametrize("B,N,d", [(8, 64, 32), (70, 333, 96), (128, 256, 128)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["dot", "l1"])
def test_scoring_sweep(B, N, d, dtype, mode, rng):
    q = jnp.asarray(rng.normal(size=(B, d)), dtype)
    e = jnp.asarray(rng.normal(size=(N, d)), dtype)
    out = ops.scoring(q, e, gamma=1.5, mode=mode, interpret=True)
    ref = scoring_ref(q.astype(jnp.float32), e.astype(jnp.float32), gamma=1.5, mode=mode)
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol * d)


@pytest.mark.parametrize("n,k,d,hd", [(16, 2, 32, 64), (100, 3, 64, 128), (64, 4, 128, 128)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_intersect_sweep(n, k, d, hd, dtype, rng):
    x = jnp.asarray(rng.normal(size=(n, k, d)), dtype)
    w1 = jnp.asarray(rng.normal(size=(d, hd)) * 0.2, jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(hd,)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(hd, 1)) * 0.2, jnp.float32)
    b2 = jnp.zeros((1,), jnp.float32)
    out = ops.intersect(x, w1, b1, w2, b2, interpret=True)
    ref = intersect_ref(x.astype(jnp.float32), w1, b1, w2, b2)
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("E,d,dl,dp,n", [(40, 16, 32, 16, 8), (100, 64, 128, 32, 33)])
def test_gather_fuse_sweep(E, d, dl, dp, n, rng):
    ids = jnp.asarray(rng.integers(0, E, n), jnp.int32)
    h_str = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
    h_sem = jnp.asarray(rng.normal(size=(E, dl)), jnp.float32)
    wp = jnp.asarray(rng.normal(size=(dl, dp)) * 0.2, jnp.float32)
    bp = jnp.asarray(rng.normal(size=(dp,)) * 0.1, jnp.float32)
    wf = jnp.asarray(rng.normal(size=(d + dp, d)) * 0.2, jnp.float32)
    bf = jnp.zeros((d,), jnp.float32)
    # n=33 is not a multiple of the row block: wrapper must pad internally
    pad = (-n) % 8
    ids_p = jnp.concatenate([ids, jnp.zeros((pad,), jnp.int32)]) if pad else ids
    out = ops.gather_fuse(ids_p, h_str, h_sem, wp, bp, wf, bf, interpret=True)[:n]
    ref = gather_fuse_ref(ids, h_str, h_sem, wp, bp, wf, bf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_gather_fuse_edge_tile(dtype, rng):
    """rows=1 DMAs the aligned sublane tile holding each row (8 f32 rows,
    16 bf16). Tables whose row counts are not multiples of that end in a
    partial tile: ids in the first, second and last tile of each table must
    come back exactly as the reference gives them."""
    E, S, d, dl, dp = 13, 21, 16, 32, 8
    ids = jnp.asarray([0, 7, 8, E - 1, E - 2, 5, 1, 9], jnp.int32)
    sem_ids = jnp.asarray([S - 1, 0, 7, 8, 15, 16, S - 2, S - 1], jnp.int32)
    h_str = jnp.asarray(rng.normal(size=(E, d)), dtype)
    h_sem = jnp.asarray(rng.normal(size=(S, dl)), dtype)
    wp = jnp.asarray(rng.normal(size=(dl, dp)) * 0.2, jnp.float32)
    bp = jnp.asarray(rng.normal(size=(dp,)) * 0.1, jnp.float32)
    wf = jnp.asarray(rng.normal(size=(d + dp, d)) * 0.2, jnp.float32)
    bf = jnp.asarray(rng.normal(size=(d,)) * 0.1, jnp.float32)
    out = ops.gather_fuse(ids, h_str, h_sem, wp, bp, wf, bf, sem_ids=sem_ids,
                          rows=1, interpret=True)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    ref = gather_fuse_ref(jnp.arange(len(ids)), f32(h_str)[ids],
                          f32(h_sem)[sem_ids], wp, bp, wf, bf)
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_gather_fuse_matches_model_path(tiny_kg, rng):
    """The kernel must agree with QueryEncoder.fused_entity_vec (Eq. 12)."""
    import jax

    from repro.models import ModelConfig, make_model

    table = rng.normal(size=(tiny_kg.n_entities, 24)).astype(np.float32)
    model = make_model("gqe", ModelConfig(dim=16, semantic_dim=24, semantic_proj_dim=8))
    params = model.init_params(jax.random.PRNGKey(0), tiny_kg.n_entities,
                               tiny_kg.n_relations, semantic_table=table)
    ids = jnp.asarray(rng.integers(0, tiny_kg.n_entities, 16), jnp.int32)
    ref = model.fused_entity_vec(params, ids)
    out = ops.gather_fuse(ids, params["entity"], params["sem_table"],
                          params["sem_proj_w"], params["sem_proj_b"],
                          params["fuse_w"], params["fuse_b"], interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
