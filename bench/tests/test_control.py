"""The control, the plain reference in bfloat16 put in the program's place,
and the planted faults each fail at least one of a cell's numbers (small
size, CPU)."""
import pytest
import tiny
from bench import checks


@pytest.mark.parametrize("cell", ["gqe-fb237.train-online",
                                  "betae-fb237.train-online"])
def test_training_control_fails(monkeypatch, cell):
    tiny.shrink(monkeypatch)
    from bench import harness
    from bench.tools import control

    b = harness.find_cell(harness.load_benchmark(), cell)
    cfg, mix = harness.load_config(b["config"]), harness.load_traffic(
        b["traffic"])
    kg = harness.build_graph(cfg)
    out = control.train_control(cfg, mix, kg, seed=4294967311)
    lim = checks.limits(cell)
    for name in ("control_bf16", "half_batch", "unchanged_state"):
        assert any(v > lim[k] for k, v in out[name].items() if k in lim), (
            name, out)
