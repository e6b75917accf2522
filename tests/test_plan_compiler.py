"""Plan-IR compiler tests (DESIGN.md §Compiler): cross-query subexpression
sharing must be SEMANTICALLY INVISIBLE — bitwise-identical encode outputs vs
the no-CSE ablation — while strictly shrinking pooled rows and peak slot
liveness, with schedule caching keyed on the deduped topology."""
import jax
import numpy as np
import pytest

from repro.core import (PooledExecutor, build_plan, compile_batch,
                        plan_to_dag, schedule)
from repro.core.patterns import QueryInstance
from repro.models import ModelConfig, make_model, model_names


def _overlap_batch(rng, n, n_entities=40, n_relations=6, anchor_pool=6,
                   rel_pool=3):
    """Random mixed batch drawing anchors/relations from SMALL pools so
    prefix chains collide across queries (the 2p/3p/ip/pi overlap case)."""
    anchors = rng.integers(0, n_entities, size=anchor_pool)
    rels = rng.integers(0, n_relations, size=rel_pool)
    patterns = ["1p", "2p", "3p", "2i", "pi", "ip", "2u", "2in"]
    out = []
    from repro.core.patterns import TEMPLATES

    for _ in range(n):
        pat = patterns[rng.integers(len(patterns))]
        tpl = TEMPLATES[pat]
        out.append(QueryInstance(
            pat,
            anchors[rng.integers(anchor_pool, size=tpl.n_anchors)].copy(),
            rels[rng.integers(rel_pool, size=tpl.n_relations)].copy(),
        ))
    return out


# ------------------------------------------------------------------ CSE core
def test_cse_encode_bitwise_property():
    """Property over seeded random overlapping batches: encode with CSE on
    == off, BITWISE, and peak slots with CSE <= without."""
    model = make_model("gqe", ModelConfig(dim=8))
    params = model.init_params(jax.random.PRNGKey(0), 40, 6)
    ex_on = PooledExecutor(model, b_max=16, cse=True)
    ex_off = PooledExecutor(model, b_max=16, cse=False)
    rng = np.random.default_rng(7)
    saved_any = False
    for trial in range(8):
        queries = _overlap_batch(rng, n=int(rng.integers(2, 20)))
        p_on = ex_on.prepare(queries)
        p_off = ex_off.prepare(queries)
        assert p_on.sched.n_slots <= p_off.sched.n_slots
        assert p_on.report.nodes_after <= p_on.report.nodes_before
        assert p_on.report.nodes_before == p_off.report.nodes_before
        assert p_off.report.pooled_rows_saved == 0
        saved_any |= p_on.report.pooled_rows_saved > 0
        a = np.asarray(ex_on.encode(params, queries))
        b = np.asarray(ex_off.encode(params, queries))
        assert np.array_equal(a, b), f"trial {trial}: CSE changed the bits"
    assert saved_any, "overlap workload never shared a subexpression"


@pytest.mark.parametrize("name", model_names())
def test_cse_encode_bitwise_all_families(name):
    model = make_model(name, ModelConfig(dim=8))
    params = model.init_params(jax.random.PRNGKey(1), 40, 6)
    queries = _overlap_batch(np.random.default_rng(3), n=12)
    on = np.asarray(PooledExecutor(model, b_max=16, cse=True).encode(params, queries))
    off = np.asarray(PooledExecutor(model, b_max=16, cse=False).encode(params, queries))
    assert np.array_equal(on, off)


def test_duplicate_queries_alias_one_answer_slot():
    """Exact-duplicate queries collapse to ONE subtree; every duplicate's
    answer-map entry aliases the same workspace slot and the final gather
    fans the single computed row out per query."""
    q = QueryInstance("2p", np.array([3]), np.array([1, 2]))
    other = QueryInstance("1p", np.array([5]), np.array([0]))
    plan = compile_batch([q, q, other, q], model_name="m")
    assert plan.report.nodes_before == 3 * 3 + 2
    assert plan.report.nodes_after == 3 + 2
    slots = plan.answer_slots[np.argsort(plan.order)]  # original order
    assert slots[0] == slots[1] == slots[3]
    assert slots[2] != slots[0]


def test_shared_prefix_interns_subchain():
    """A 1p query that is the prefix of a co-batched 2p shares the 2p's
    EMBED and first PROJECT nodes."""
    two_p = QueryInstance("2p", np.array([4]), np.array([1, 2]))
    one_p = QueryInstance("1p", np.array([4]), np.array([1]))
    plan = build_plan([one_p, two_p])
    assert plan.nodes_before == 2 + 3
    assert plan.n_nodes == 3          # E(4), P(1), P(2)
    # the 1p answer is the 2p's intermediate node
    dag = plan_to_dag(plan)
    assert dag.answer_node[0] in dag.inputs[dag.answer_node[1]]
    # shared nodes keep their slots live for every consumer (Eq. 7 across
    # queries): the schedule must still be executable
    sched = schedule(dag, b_max=8)
    assert sched.n_nodes == 3


def test_topology_key_shared_across_bindings():
    """Two batches with different entity/relation ids but the same deduped
    SHAPE share one schedule-cache entry; a batch whose sharing pattern
    differs does not."""
    ex = PooledExecutor(make_model("gqe", ModelConfig(dim=8)), b_max=16)
    b1 = [QueryInstance("1p", np.array([0]), np.array([0])),
          QueryInstance("1p", np.array([1]), np.array([1]))]
    b2 = [QueryInstance("1p", np.array([2]), np.array([2])),
          QueryInstance("1p", np.array([3]), np.array([3]))]
    p1 = ex.prepare(b1)
    p2 = ex.prepare(b2)
    assert p1.structure_key == p2.structure_key
    assert len(ex._sched_cache) == 1
    assert ex._sched_cache.stats()["hits"] == 1
    # same two queries but now duplicates -> different post-CSE shape
    b3 = [QueryInstance("1p", np.array([5]), np.array([4])),
          QueryInstance("1p", np.array([5]), np.array([4]))]
    p3 = ex.prepare(b3)
    assert p3.structure_key != p1.structure_key
    assert len(ex._sched_cache) == 2


def test_topology_key_permutation_invariant(mixed_queries):
    """Canonical full-key ordering makes permuted batches compile to the
    identical plan (one cache entry, same program signature)."""
    ex = PooledExecutor(make_model("gqe", ModelConfig(dim=8)), b_max=32)
    queries = [b.query for b in mixed_queries]
    p1 = ex.prepare(queries)
    p2 = ex.prepare(list(reversed(queries)))
    assert p1.structure_key == p2.structure_key
    assert p1.signature == p2.signature
    assert len(ex._sched_cache) == 1


def test_order_restored_with_duplicates():
    """encode() returns rows in ORIGINAL submission order even when CSE
    aliased some of them."""
    model = make_model("q2b", ModelConfig(dim=8))
    params = model.init_params(jax.random.PRNGKey(0), 40, 6)
    ex = PooledExecutor(model, b_max=16)
    qa = QueryInstance("1p", np.array([7]), np.array([2]))
    qb = QueryInstance("2p", np.array([7]), np.array([2, 3]))
    out = np.asarray(ex.encode(params, [qb, qa, qb, qa]))
    assert np.array_equal(out[0], out[2])
    assert np.array_equal(out[1], out[3])
    assert not np.array_equal(out[0], out[1])
    solo = np.asarray(ex.encode(params, [qa]))
    assert np.array_equal(out[1], solo[0])


def test_bind_arrays_match_per_step_gather(mixed_queries):
    """The vectorized bind rebuild (one gather + flat scatter) must equal
    the per-step formula it replaced."""
    queries = [b.query for b in mixed_queries]
    plan = compile_batch(queries, model_name="m", b_max=32)
    dag = plan_to_dag(build_plan([queries[i] for i in plan.order]))
    for s, bind in zip(plan.sched.steps, plan.bind_arrays):
        want_rel = np.zeros(s.padded_n, dtype=np.int64)
        want_rel[: s.n] = dag.rel[s.node_ids].clip(min=0)
        want_anc = np.zeros(s.padded_n, dtype=np.int64)
        want_anc[: s.n] = dag.anchor[s.node_ids].clip(min=0)
        assert np.array_equal(bind["rel_ids"], want_rel)
        assert np.array_equal(bind["anchor_ids"], want_anc)
        assert bind["rel_ids"].dtype == np.int64


def test_no_cse_keeps_per_query_nodes(mixed_queries):
    from repro.core.patterns import TEMPLATES

    queries = [b.query for b in mixed_queries]
    plan = compile_batch(queries, model_name="m", b_max=32, cse=False)
    want = sum(len(TEMPLATES[q.pattern].nodes) for q in queries)
    assert plan.report.nodes_after == want
    assert plan.report.pooled_rows_saved == 0
    assert plan.sched.n_nodes == want


def test_compile_empty_batch():
    plan = compile_batch([], model_name="m")
    assert plan.sched.steps == []
    assert len(plan.answer_slots) == 0
    assert plan.report.nodes_before == 0


# ----------------------------------------- overlap-heavy sampled workload
_PREFIX = {"3p": "2p", "2p": "1p"}  # chain pattern -> its prefix pattern


def _sampled_overlap_batches(kg, n_batches, batch_size, seed=13):
    """Half freshly sampled chain/branch queries, then the prefix of each
    chain (a 1p that IS the first hop of a co-batched 2p, ...), then
    repeats: the overlap profile of serving traffic, where popular
    subqueries recur across concurrent requests."""
    from repro.core.patterns import answer_query
    from repro.sampling import OnlineSampler
    from repro.sampling.online import SampledQuery

    sampler = OnlineSampler(kg, patterns=("1p", "2p", "3p", "ip", "pi", "2i"),
                            seed=seed)
    batches = []
    for _ in range(n_batches):
        base = sampler.sample_batch(batch_size // 2)
        batch = list(base)
        for b in base:
            pre = _PREFIX.get(b.query.pattern)
            if pre is not None:
                n_rel = 1 if pre == "1p" else 2
                pq = QueryInstance(pre, b.query.anchors[:1].copy(),
                                   b.query.relations[:n_rel].copy())
                batch.append(SampledQuery(
                    pq, np.fromiter(answer_query(kg, pq), np.int64)))
        batch += [base[i % len(base)] for i in range(batch_size - len(batch))]
        batches.append(batch[:batch_size])
    return batches


def test_cse_saves_a_quarter_of_pooled_rows_on_overlap_traffic(tiny_kg):
    """Cross-query CSE merges at least 25% of the pooled rows of the
    overlap-heavy workload."""
    before = after = 0
    for batch in _sampled_overlap_batches(tiny_kg, 4, 32):
        plan = compile_batch([s.query for s in batch], model_name="m")
        before += plan.report.nodes_before
        after += plan.report.nodes_after
    assert (before - after) / before >= 0.25, (before, after)


@pytest.mark.parametrize("name", model_names())
def test_cse_training_losses_match_no_cse(tiny_kg, name):
    """Training on the overlap workload with CSE on vs off: the first step
    runs from identical params, so its loss is bitwise equal; later steps
    may differ by the reassociated sum of a shared node's cotangents, so
    the whole sequence stays within 1e-5."""
    from repro.training import AdamConfig, NGDBTrainer, TrainConfig

    batches = _sampled_overlap_batches(tiny_kg, 1, 16)
    losses = {}
    for cse in (True, False):
        cfg = TrainConfig(batch_size=16, n_negatives=4, b_max=32, seed=0,
                          adam=AdamConfig(lr=1e-3), cse=cse)
        tr = NGDBTrainer(make_model(name, ModelConfig(dim=8, gamma=6.0)),
                         tiny_kg, cfg)
        tr.train(2, log_every=0, batches=batches)
        losses[cse] = [h["loss"] for h in tr.history]
    assert losses[True][0] == losses[False][0], losses
    np.testing.assert_allclose(losses[True], losses[False], rtol=0, atol=1e-5)
