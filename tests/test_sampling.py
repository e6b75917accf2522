import numpy as np
import pytest

from repro.core import PATTERN_NAMES, TEMPLATES, OpType, QueryInstance, answer_query
from repro.data import generate_synthetic_kg
from repro.sampling import AdaptiveDistribution, OnlineSampler, SampledQuery


def test_all_patterns_sampleable(tiny_kg):
    s = OnlineSampler(tiny_kg, seed=3)
    for pat in PATTERN_NAMES:
        sq = s.sample(pat)
        assert sq.query.pattern == pat
        assert len(sq.answers) > 0
        # rejection guarantee: oracle agrees the answers are non-empty
        assert set(sq.answers.tolist()) <= answer_query(tiny_kg, sq.query)


def test_batch_distribution(tiny_kg):
    s = OnlineSampler(tiny_kg, patterns=("1p", "2i"), seed=0)
    batch = s.sample_batch(64, dist={"1p": 1.0, "2i": 0.0})
    assert all(b.query.pattern == "1p" for b in batch)


def test_training_arrays_negative_filtering(tiny_kg):
    s = OnlineSampler(tiny_kg, seed=1)
    batch = s.sample_batch(16)
    queries, pos, neg = s.to_training_arrays(batch, n_negatives=8)
    assert pos.shape == (16,) and neg.shape == (16, 8)
    for i, b in enumerate(batch):
        assert pos[i] in b.answers
        assert not np.isin(neg[i], b.answers).any()


def test_adaptive_shifts_toward_hard():
    ad = AdaptiveDistribution(["1p", "2i", "3p"], ema=0.5, uniform_floor=0.2)
    for _ in range(10):
        ad.update({"1p": 0.1, "2i": 5.0, "3p": 0.1})
    d = ad.distribution()
    assert d["2i"] > d["1p"]
    assert d["2i"] > 1 / 3
    assert abs(sum(d.values()) - 1.0) < 1e-9
    # uniform floor keeps everything sampleable
    assert min(d.values()) >= 0.2 / 3 - 1e-9


def test_sampler_determinism(tiny_kg):
    a = OnlineSampler(tiny_kg, seed=42).sample_batch(8)
    b = OnlineSampler(tiny_kg, seed=42).sample_batch(8)
    for x, y in zip(a, b):
        assert x.query.key() == y.query.key()


class _SetOracleSampler:
    """The sampler as it was before its answer table and array oracle:
    ``Generator.choice(..., p=...)`` per witness draw and rejection on
    ``answer_query``'s Python sets. The reference stream for the tests
    below."""

    def __init__(self, kg, patterns, seed, max_answers, degree_weighted):
        self.kg = kg
        self.patterns = list(patterns)
        self.rng = np.random.default_rng(seed)
        self.max_answers = max_answers
        self._in_indptr, self._in_rels, self._in_heads = kg.incoming_by_tail
        cand = kg.entities_with_incoming
        if degree_weighted:
            w = kg.degree[cand].astype(np.float64)
            self._answer_p = w / w.sum()
        else:
            self._answer_p = None
        self._answer_cand = cand
        self.stats = {"sampled": 0, "rejected": 0}
        self.weighted_draws = 0

    def _witness(self):
        self.weighted_draws += self._answer_p is not None
        return int(self.rng.choice(self._answer_cand, p=self._answer_p))

    def _random_incoming(self, ent):
        lo, hi = self._in_indptr[ent], self._in_indptr[ent + 1]
        if hi <= lo:
            return None
        j = int(self.rng.integers(lo, hi))
        return int(self._in_rels[j]), int(self._in_heads[j])

    def _ground(self, pattern):
        tpl = TEMPLATES[pattern]
        n = len(tpl.nodes)
        ent = np.full(n, -1, dtype=np.int64)
        rel_of_node = np.full(n, -1, dtype=np.int64)
        ent[tpl.answer_node] = self._witness()
        for i in range(n - 1, -1, -1):
            node = tpl.nodes[i]
            if ent[i] < 0:
                ent[i] = self._witness()
            if node.op == OpType.PROJECT:
                step = self._random_incoming(int(ent[i]))
                if step is None:
                    return None
                rel_of_node[i], ent[node.inputs[0]] = step
            elif node.op == OpType.INTERSECT:
                for j in node.inputs:
                    if tpl.nodes[j].op != OpType.NEGATE:
                        ent[j] = ent[i]
            elif node.op == OpType.UNION:
                k = node.inputs[int(self.rng.integers(len(node.inputs)))]
                ent[k] = ent[i]
        anchors = np.array(
            [ent[i] for i, nd in enumerate(tpl.nodes) if nd.op == OpType.EMBED], dtype=np.int64)
        rels = np.array(
            [rel_of_node[i] for i, nd in enumerate(tpl.nodes) if nd.op == OpType.PROJECT],
            dtype=np.int64)
        if (anchors < 0).any() or (rels < 0).any():
            return None
        return QueryInstance(pattern, anchors, rels)

    def sample(self, pattern):
        for _ in range(32):
            self.stats["sampled"] += 1
            q = self._ground(pattern)
            if q is None:
                self.stats["rejected"] += 1
                continue
            ans = answer_query(self.kg, q)
            if not ans:
                self.stats["rejected"] += 1
                continue
            ans_arr = np.fromiter(ans, dtype=np.int64)
            if len(ans_arr) > self.max_answers:
                ans_arr = self.rng.choice(ans_arr, self.max_answers, replace=False)
            return q, ans_arr
        raise RuntimeError(pattern)

    def sample_batch(self, batch_size):
        picks = self.rng.choice(len(self.patterns), size=batch_size)
        return [self.sample(self.patterns[i]) for i in picks]


@pytest.fixture(scope="module")
def hub_kg():
    """Few relations and steep hubs: large answer sets and rejections."""
    return generate_synthetic_kg(400, 6, 5000, seed=7, hub_exponent=1.3)


@pytest.mark.parametrize("degree_weighted", [True, False])
@pytest.mark.parametrize("seed", [0, 11, 2**31 + 5])
def test_query_stream_matches_set_oracle_sampler(hub_kg, seed, degree_weighted):
    max_answers = 24  # small, so sub-sampled answer sets occur in the stream
    new = OnlineSampler(hub_kg, seed=seed, max_answers=max_answers,
                        degree_weighted=degree_weighted)
    old = _SetOracleSampler(hub_kg, PATTERN_NAMES, seed, max_answers, degree_weighted)
    got, want = [], []
    for round_ in range(3):
        for pat in PATTERN_NAMES:
            got.append(new.sample(pat))
            want.append(old.sample(pat))
        got.extend(new.sample_batch(40))
        want.extend(old.sample_batch(40))
    n_capped = 0
    for sq, (q, ans) in zip(got, want):
        assert sq.query.key() == q.key()
        full = answer_query(hub_kg, q)
        if len(full) > max_answers:  # same draw count, another subset
            n_capped += 1
            assert len(sq.answers) == len(ans) == max_answers
            assert set(sq.answers.tolist()) <= full
        else:
            assert np.array_equal(sq.answers, np.array(sorted(full), dtype=np.int64))
    assert n_capped > 0
    assert {k: new.stats[k] for k in ("sampled", "rejected")} == old.stats
    assert old.stats["rejected"] > 0
    # Every weighted witness draw: one per grounding attempt, plus one per
    # node left unconstrained (negated, union and pi-type branches).
    assert new.stats["table_draws"] == old.weighted_draws
    if degree_weighted:
        assert old.weighted_draws > old.stats["sampled"]
    else:
        assert old.weighted_draws == 0


def _isin_training_arrays(rng, n_entities, batch, n_negatives, counts):
    """``OnlineSampler.to_training_arrays`` as it was before the batched
    membership check: one ``np.isin`` per query. The reference stream for
    the tests below; ``counts`` records what its resample loop did."""
    pos = np.array([b.answers[rng.integers(len(b.answers))] for b in batch])
    neg = rng.integers(0, n_entities, size=(len(batch), n_negatives))
    for i, b in enumerate(batch):
        bad = np.isin(neg[i], b.answers)
        counts["neg_rows_resampled"] += bool(bad.any())
        rounds = 0
        while bad.any():  # resample collisions (rare on sparse graphs)
            counts["neg_redraws"] += int(bad.sum())
            rounds += 1
            neg[i, bad] = rng.integers(0, n_entities, bad.sum())
            bad = np.isin(neg[i], b.answers)
        counts["max_rounds"] = max(counts["max_rounds"], rounds)
    return [b.query for b in batch], pos, neg


@pytest.fixture(scope="module")
def dense_kg():
    """Few entities and many edges: answer sets cover much of the graph."""
    return generate_synthetic_kg(40, 3, 900, seed=5, hub_exponent=1.3)


def _batch(case, s):
    if case != "descending":
        return s.sample_batch(48 if case == "dense" else 64)
    # Hand-built: both end entities, given in descending order.
    e = s.kg.n_entities
    return [SampledQuery(b.query, np.array(ans, dtype=np.int64))
            for b, ans in zip(s.sample_batch(6), (
                [e - 1, 0], [e - 1], [0], [e - 1, e // 2, 3, 0],
                list(range(e - 1, -1, -1))[: e // 2], [e - 1, 1, 0]))]


@pytest.mark.parametrize("case", ["dense", "unsorted", "descending"])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_training_arrays_match_per_row_isin(dense_kg, hub_kg, case, seed):
    kg = hub_kg if case == "unsorted" else dense_kg
    s = OnlineSampler(kg, seed=seed, max_answers=24 if case == "unsorted" else 512)
    batch = _batch(case, s)
    if case == "unsorted":
        assert any((np.diff(b.answers) < 0).any() for b in batch)
    ref_rng = np.random.default_rng()
    ref_rng.bit_generator.state = s.rng.bit_generator.state
    counts = {"neg_rows_resampled": 0, "neg_redraws": 0, "max_rounds": 0}
    before = dict(s.stats)
    queries, pos, neg = s.to_training_arrays(batch, n_negatives=32)
    want_q, want_pos, want_neg = _isin_training_arrays(
        ref_rng, kg.n_entities, batch, 32, counts)
    assert queries == want_q
    assert pos.dtype == want_pos.dtype == np.int64 and np.array_equal(pos, want_pos)
    assert neg.dtype == want_neg.dtype == np.int64 and np.array_equal(neg, want_neg)
    # The generator was consumed exactly as the per-row loop consumed it.
    assert s.rng.bit_generator.state == ref_rng.bit_generator.state
    for k in ("neg_rows_resampled", "neg_redraws"):
        assert s.stats[k] - before[k] == counts[k]
    assert counts["neg_rows_resampled"] > 0
    if case == "dense":
        assert counts["max_rounds"] >= 2
    for i, b in enumerate(batch):
        assert pos[i] in b.answers
        assert not np.isin(neg[i], b.answers).any()
