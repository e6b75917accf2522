import numpy as np
import pytest

from repro.core import (
    OpType,
    PATTERN_NAMES,
    TEMPLATES,
    QueryInstance,
    answer_query,
    build_batched_dag,
)
from repro.core import patterns as patterns_mod
from repro.core.patterns import answer_array
from repro.data import generate_synthetic_kg


def test_fourteen_patterns():
    assert len(TEMPLATES) == 14
    assert set(PATTERN_NAMES) == {
        "1p", "2p", "3p", "2i", "3i", "pi", "ip", "2u", "up",
        "2in", "3in", "pin", "pni", "inp",
    }


def test_templates_well_formed():
    for name, tpl in TEMPLATES.items():
        for i, node in enumerate(tpl.nodes):
            for j in node.inputs:
                assert j < i, f"{name}: forward reference"
            if node.op == OpType.EMBED:
                assert not node.inputs
            elif node.op in (OpType.PROJECT, OpType.NEGATE):
                assert len(node.inputs) == 1
            else:
                assert len(node.inputs) >= 2
        # negation only ever feeds intersection in these patterns
        for i, node in enumerate(tpl.nodes):
            if node.op == OpType.NEGATE:
                consumers = [
                    m for m in tpl.nodes if i in m.inputs
                ]
                assert all(c.op == OpType.INTERSECT for c in consumers)


def test_answer_query_1p(tiny_kg):
    q = QueryInstance("1p", np.array([5]), np.array([1]))
    assert answer_query(tiny_kg, q) == set(tiny_kg.neighbors(5, 1).tolist())


def test_answer_query_2i_bruteforce(tiny_kg):
    q = QueryInstance("2i", np.array([3, 7]), np.array([0, 1]))
    expected = set(tiny_kg.neighbors(3, 0).tolist()) & set(
        tiny_kg.neighbors(7, 1).tolist()
    )
    assert answer_query(tiny_kg, q) == expected


def test_answer_query_2in(tiny_kg):
    q = QueryInstance("2in", np.array([3, 7]), np.array([0, 1]))
    expected = set(tiny_kg.neighbors(3, 0).tolist()) - set(
        tiny_kg.neighbors(7, 1).tolist()
    )
    assert answer_query(tiny_kg, q) == expected


def test_answer_query_up(tiny_kg):
    q = QueryInstance("up", np.array([3, 7]), np.array([0, 1, 2]))
    u = set(tiny_kg.neighbors(3, 0).tolist()) | set(tiny_kg.neighbors(7, 1).tolist())
    expected = set(
        tiny_kg.neighbors_of_set(np.fromiter(u, dtype=np.int64), 2).tolist()
    )
    assert answer_query(tiny_kg, q) == expected


@pytest.fixture(scope="module")
def zipf_kg():
    return generate_synthetic_kg(2000, 20, 30000, seed=1, hub_exponent=1.0)


def _mixed_instances(kg, pattern, seed):
    """Sampled queries (non-empty answers) and uniformly drawn ones (mostly
    empty) of one template."""
    from repro.sampling import OnlineSampler

    tpl = TEMPLATES[pattern]
    sampler = OnlineSampler(kg, patterns=[pattern], seed=seed)
    qs = [sampler.sample(pattern).query for _ in range(20)]
    rng = np.random.default_rng(seed)
    for _ in range(40):
        qs.append(QueryInstance(
            pattern,
            rng.integers(0, kg.n_entities, tpl.n_anchors),
            rng.integers(0, kg.n_relations, tpl.n_relations)))
    return qs


@pytest.mark.parametrize("pattern", PATTERN_NAMES)
def test_answer_array_matches_answer_query(pattern, tiny_kg, zipf_kg, monkeypatch):
    head_counts = []
    project = patterns_mod._project_array

    def spy(adj, n_relations, heads, r):
        head_counts.append(len(heads))
        return project(adj, n_relations, heads, r)

    monkeypatch.setattr(patterns_mod, "_project_array", spy)
    n_empty = n_full = 0
    for kg, seed in ((tiny_kg, 5), (zipf_kg, 6)):
        for q in _mixed_instances(kg, pattern, seed):
            got = answer_array(kg, q)
            assert got.dtype == np.int64 and got.ndim == 1
            assert np.all(np.diff(got) > 0)  # sorted, unique
            assert set(got.tolist()) == answer_query(kg, q)
            n_empty += len(got) == 0
            n_full += len(got) > 0
    assert n_empty and n_full
    tpl = TEMPLATES[pattern]
    chained = any(tpl.nodes[n.inputs[0]].op != OpType.EMBED
                  for n in tpl.nodes if n.op == OpType.PROJECT)
    if chained:  # a projection over a set of several heads was evaluated
        assert max(head_counts) > 1


def test_dag_merge_counts(mixed_queries):
    queries = [b.query for b in mixed_queries]
    dag = build_batched_dag(queries)
    expected_nodes = sum(len(TEMPLATES[q.pattern].nodes) for q in queries)
    assert dag.n_nodes == expected_nodes
    assert dag.n_queries == len(queries)
    # anchors/relations wired in template order
    for qi, q in enumerate(queries):
        mask = dag.query_id == qi
        anchors = dag.anchor[mask]
        assert np.array_equal(anchors[anchors >= 0], q.anchors)
        rels = dag.rel[mask]
        assert np.array_equal(rels[rels >= 0], q.relations)


def test_structure_key_order_invariant(mixed_queries):
    queries = [b.query for b in mixed_queries]
    k1 = build_batched_dag(queries).structure_key()
    k2 = build_batched_dag(list(reversed(queries))).structure_key()
    assert k1 == k2
