"""Operations a query needs, counted from its template tree before CSE and
without padding, at the family's widths. The count is the same whatever
implements the step, so CSE, padding or a kernel move ``mfu`` only through
time.

* Every matrix product of the operators: 2 m k n.
* The distance of one (query, candidate) pair: one per element for each
  elementwise operation in it, each special function (``lgamma``,
  ``digamma``, ``softplus``) counted as one, the sum over the width as one
  per element.
* Training: forward + backward = 3 x forward. Adam is not counted.

Per family (d = dim, h = hidden_mult * d):

gqe: project 0; intersect over k inputs 4 k d h + 2 d^2 (phi on each
input, rho once); union 0; negate 4 d h; distance |q - e|_1 = 3 d
(subtract, abs, sum).

betae: project 2 (3d) h + 2 h (2d) = 10 d h; intersect and union over k
inputs k (2 (2d) h + 2 h) (attention MLP per input); negate 0; distance
40 d: the entity's lift (alpha: scale, softplus, add, clip = 4; beta:
negate, scale, softplus, add, clip = 5), the query's clip (2), the KL (two
betaln of a sum, three lgamma and two adds = 12; two terms (x - y)
digamma(x) = 6; the third term's three-way difference, sum, digamma and
product = 6; four adds combining them = 4) and the sum over the width (1).
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench import harness

# Elementwise operations per element of one (query, candidate) distance.
_DIST_PER_ELEM = {"gqe": 3, "betae": 9 + 2 + 28 + 1}


def operator_flops(family: str, m: Dict, pattern: str) -> float:
    """Matmul operations of one query's operators (forward)."""
    d = m["dim"]
    h = d * m["hidden_mult"]
    total = 0.0
    for op, inputs in harness.TEMPLATES[pattern]:
        k = len(inputs)
        if family == "gqe":
            if op == "I":
                total += 4 * k * d * h + 2 * d * d
            elif op == "N":
                total += 4 * d * h
        elif family == "betae":
            if op == "P":
                total += 2 * (3 * d) * h + 2 * h * (2 * d)
            elif op in ("I", "U"):
                total += k * (2 * (2 * d) * h + 2 * h)
        else:
            raise ValueError(f"no operation count for family {family!r}")
    return total


def distance_ops(family: str, m: Dict) -> float:
    return _DIST_PER_ELEM[family] * m["dim"]


def query_ops(family: str, m: Dict, pattern: str, candidates: int) -> float:
    """Forward operations of one query scored against ``candidates``."""
    return (operator_flops(family, m, pattern)
            + candidates * distance_ops(family, m))


def train_step_ops(family: str, m: Dict, patterns: Iterable[str],
                   n_negatives: int) -> float:
    """Forward + backward of one training step over ``patterns``."""
    fwd = sum(query_ops(family, m, p, 1 + n_negatives) for p in patterns)
    return 3.0 * fwd
