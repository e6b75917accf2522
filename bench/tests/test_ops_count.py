"""ops_count against counts made by hand at dim 400, hidden 800."""
import pytest

import tiny  # noqa: F401  (puts the repository on sys.path)
from bench import ops_count

M = {"dim": 400, "hidden_mult": 2}

# gqe: negate 4dh = 1,280,000; intersect over 2 inputs 4*2*d*h + 2d^2 =
# 2,560,000 + 320,000; projection and union none. betae: projection
# 2*1200*800 + 2*800*800 = 3,200,000; intersect over 2 inputs
# 2*(2*800*800 + 2*800) = 2,563,200; negation none.
HAND = {
    ("gqe", "1p"): 0,
    ("gqe", "2in"): 1_280_000 + 2_880_000,
    ("gqe", "pi"): 2_880_000,
    ("betae", "1p"): 3_200_000,
    ("betae", "2in"): 2 * 3_200_000 + 2_563_200,
    ("betae", "pi"): 3 * 3_200_000 + 2_563_200,
}


@pytest.mark.parametrize("family,pattern", sorted(HAND))
def test_operator_flops_by_hand(family, pattern):
    assert ops_count.operator_flops(family, M, pattern) == HAND[family,
                                                                pattern]


@pytest.mark.parametrize("family,per_elem", [("gqe", 3), ("betae", 40)])
def test_distance_and_train_step(family, per_elem):
    d = ops_count.distance_ops(family, M)
    assert d == per_elem * 400
    # 1p with 64 negatives: 65 distances forward, x3 with backward.
    want = 3 * (HAND[family, "1p"] + 65 * per_elem * 400)
    assert ops_count.train_step_ops(family, M, ["1p"], 64) == want
