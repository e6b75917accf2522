"""A whole run past the look for a chip, at a small size on the CPU, with
the timed path broken underneath: ``correct`` must come out false. The
cells run on one chip, so there is no exchange between chips to leave out.
"""
import pytest
import tiny

CELLS = ("gqe-fb237.train-online", "betae-fb237.train-online")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_runs_are_correct(monkeypatch, capsys, cell):
    assert tiny.run_cell(monkeypatch, capsys, cell)["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(monkeypatch, capsys, cell):
    tiny.shrink(monkeypatch)
    from repro.training import loop

    monkeypatch.setattr(loop, "adam_update",
                        lambda grads, state, params, cfg: (params, state))
    out = tiny.run_cell(monkeypatch, capsys, cell)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] > 0.9


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out(monkeypatch, capsys, cell):
    tiny.shrink(monkeypatch)
    from repro.training import loop

    real = loop.negative_sampling_loss

    def half(model, params, q, pos, neg):
        n = q.shape[0] // 2
        return real(model, params, q[:n], pos[:n], neg[:n])

    monkeypatch.setattr(loop, "negative_sampling_loss", half)
    out = tiny.run_cell(monkeypatch, capsys, cell)
    assert out["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_loss_altered_where_it_is_produced(monkeypatch, capsys, cell):
    tiny.shrink(monkeypatch)
    from repro.training import loop

    real = loop.negative_sampling_loss

    def altered(*a):
        loss, per_q = real(*a)
        return loss * 1.01, per_q

    monkeypatch.setattr(loop, "negative_sampling_loss", altered)
    out = tiny.run_cell(monkeypatch, capsys, cell)
    assert out["correct"] is False
