"""Online stochastic query sampler (App. F).

Queries are synthesized on-the-fly by BACKWARD ground-truth instantiation:
pick a (degree-weighted) answer entity, then walk the template DAG in reverse
assigning a witness entity to every node and drawing relations from actual
incoming edges — so accepted queries are non-empty by construction on the
positive part. Negation branches are grounded independently and validated by
rejection sampling against the symbolic oracle (P_accept ∝ 1[q ∈ Q_valid]).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.ops import OpType
from repro.core.patterns import TEMPLATES, QueryInstance, answer_array
from repro.data.kg import KnowledgeGraph


def _ground_plan(tpl) -> Tuple:
    """A template as ``_ground`` walks it: (n nodes, reverse walk of
    (node, op, inputs), EMBED nodes, PROJECT nodes). An INTERSECT lists only
    its positive inputs: negated ones stay unconstrained."""
    walk = []
    for i in range(len(tpl.nodes) - 1, -1, -1):
        node = tpl.nodes[i]
        inputs = node.inputs
        if node.op == OpType.INTERSECT:
            inputs = tuple(j for j in inputs if tpl.nodes[j].op != OpType.NEGATE)
        walk.append((i, node.op, inputs))
    ops = [nd.op for nd in tpl.nodes]
    return (len(ops), tuple(walk),
            tuple(i for i, op in enumerate(ops) if op == OpType.EMBED),
            tuple(i for i, op in enumerate(ops) if op == OpType.PROJECT))


_GROUND_PLANS = {name: _ground_plan(tpl) for name, tpl in TEMPLATES.items()}


def _in_sorted(values: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Which of ``values`` occur in ``ref`` (ascending, non-empty)."""
    at = ref.searchsorted(values).clip(max=len(ref) - 1)
    return ref[at] == values


@dataclasses.dataclass
class SampledQuery:
    query: QueryInstance
    answers: np.ndarray  # ground-truth answer ids on the training graph


class OnlineSampler:
    """The paper's App. F sampler: O(k·|B|) per batch, zero storage."""

    def __init__(
        self,
        kg: KnowledgeGraph,
        patterns: Sequence[str] = tuple(TEMPLATES),
        seed: int = 0,
        max_rejects: int = 32,
        max_answers: int = 512,
        degree_weighted: bool = True,
    ):
        self.kg = kg
        self.patterns = list(patterns)
        self.rng = np.random.default_rng(seed)
        self.max_rejects = max_rejects
        self.max_answers = max_answers
        self._in_indptr, self._in_rels, self._in_heads = kg.incoming_by_tail
        cand = kg.entities_with_incoming
        if degree_weighted:
            w = kg.degree[cand].astype(np.float64)
            # Generator.choice(cand, p=w / w.sum())'s own table, built once
            # instead of on every draw: the same index for the same
            # generator state, one rng.random() per draw.
            cdf = (w / w.sum()).cumsum()
            cdf /= cdf[-1]
            self._answer_cdf = cdf
        else:
            self._answer_cdf = None
        self._answer_cand = cand
        # table_draws: weighted answer/witness draws served by the table.
        # neg_rows_resampled: rows of to_training_arrays whose corruptions
        # hit an answer; neg_redraws: the values drawn again for them.
        self.stats = {"sampled": 0, "rejected": 0, "table_draws": 0,
                      "neg_rows_resampled": 0, "neg_redraws": 0}

    # ------------------------------------------------------------- grounding
    def _draw_answer(self) -> int:
        """A witness entity, degree-weighted unless built otherwise."""
        if self._answer_cdf is None:
            return int(self.rng.choice(self._answer_cand))
        self.stats["table_draws"] += 1
        i = self._answer_cdf.searchsorted(self.rng.random(), side="right")
        return int(self._answer_cand[i])

    def _random_incoming(self, ent: int) -> Optional[Tuple[int, int]]:
        lo, hi = self._in_indptr[ent], self._in_indptr[ent + 1]
        if hi <= lo:
            return None
        j = int(self.rng.integers(lo, hi))
        return int(self._in_rels[j]), int(self._in_heads[j])

    def _ground(self, pattern: str) -> Optional[QueryInstance]:
        n, walk, embeds, projects = _GROUND_PLANS[pattern]
        ent = [-1] * n
        rel_of_node = [-1] * n
        ent[n - 1] = self._draw_answer()  # the answer node is the last
        # Reverse walk: every node's witness entity is known before its inputs.
        for i, op, inputs in walk:
            if ent[i] < 0:
                # Unconstrained branch (e.g. the negated side): random witness.
                ent[i] = self._draw_answer()
            if op is OpType.PROJECT:
                step = self._random_incoming(ent[i])
                if step is None:
                    return None
                rel_of_node[i], ent[inputs[0]] = step
            elif op is OpType.INTERSECT:
                for j in inputs:  # the positive inputs share the witness
                    ent[j] = ent[i]
            elif op is OpType.UNION:
                k = inputs[int(self.rng.integers(len(inputs)))]
                ent[k] = ent[i]  # one branch witnesses; others stay random
            # NEGATE: its input is grounded independently (stays -1 → random)
        return QueryInstance(
            pattern,
            np.array([ent[i] for i in embeds], dtype=np.int64),
            np.array([rel_of_node[i] for i in projects], dtype=np.int64),
        )

    # ------------------------------------------------------------- sampling
    def sample(self, pattern: str) -> SampledQuery:
        for _ in range(self.max_rejects):
            self.stats["sampled"] += 1
            q = self._ground(pattern)
            if q is None:
                self.stats["rejected"] += 1
                continue
            ans_arr = answer_array(self.kg, q)  # sorted, unique
            if len(ans_arr) == 0:  # rejection sampling: require non-empty answer set
                self.stats["rejected"] += 1
                continue
            if len(ans_arr) > self.max_answers:
                ans_arr = self.rng.choice(ans_arr, self.max_answers, replace=False)
            return SampledQuery(q, ans_arr)
        raise RuntimeError(f"rejection sampling failed for pattern {pattern}")

    def sample_batch(
        self, batch_size: int, dist: Optional[Dict[str, float]] = None
    ) -> List[SampledQuery]:
        names = self.patterns
        if dist is None:
            p = None
        else:
            p = np.array([dist.get(n, 0.0) for n in names], dtype=np.float64)
            p = p / p.sum()
        picks = self.rng.choice(len(names), size=batch_size, p=p)
        return [self.sample(names[i]) for i in picks]

    # --------------------------------------------------------- train tensors
    def to_training_arrays(self, batch: List[SampledQuery], n_negatives: int):
        """(queries, positives [B], negatives [B,K]) — negatives are uniform
        corruptions filtered against the (sampled) answer set.

        The whole batch is checked at once. Row i's answers and its
        corruptions are offset by ``i * E``; with each row's corruptions
        sorted, the batch's form one ascending array, and one
        ``searchsorted`` of the sorted answer keys into it finds every row
        with a hit. Only those rows are redrawn, in row order, so the
        generator is consumed exactly as a per-row check would consume it."""
        n_ent = self.kg.n_entities
        lens = np.array([len(b.answers) for b in batch], dtype=np.int64)
        answers = np.concatenate([b.answers for b in batch])
        starts = np.concatenate(([0], lens.cumsum()))
        # integers(0, lens) draws what one integers(len) per query draws.
        pos = answers[starts[:-1] + self.rng.integers(0, lens)]
        neg = self.rng.integers(0, n_ent, size=(len(batch), n_negatives))
        row_base = np.arange(len(batch), dtype=np.int64) * n_ent
        keys = np.sort(np.repeat(row_base, lens) + answers)
        corrupt = (np.sort(neg, axis=1) + row_base[:, None]).ravel()
        rows = np.unique(keys[_in_sorted(keys, corrupt)] // n_ent) if neg.size else ()
        for i in rows:
            # Row i's keys are one contiguous run of the sorted keys.
            own = keys[starts[i]:starts[i + 1]] - row_base[i]
            bad = _in_sorted(neg[i], own)
            self.stats["neg_rows_resampled"] += 1
            while bad.any():  # resample collisions (rare on sparse graphs)
                n_bad = int(bad.sum())
                self.stats["neg_redraws"] += n_bad
                neg[i, bad] = self.rng.integers(0, n_ent, n_bad)
                bad = _in_sorted(neg[i], own)
        return [b.query for b in batch], pos, neg
