"""The host per-layer readers on a hand-built ``Context``: their values from
synthetic window spans, and None when what they read is absent."""
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (puts the repository on sys.path)
from bench import harness, layers

NAMES = ("train.negatives_ms", "train.scheduler_offcpu_ms",
         "train.queue_full_ms", "train.h2d_kb")


def _ctx(spans):
    run = SimpleNamespace(args=SimpleNamespace(seconds=20.0),
                          window_info={"steps": 2},
                          devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    return layers.Context(run, spans, None)


def _x(name, dur, tdur=None, step=0, **args):
    ev = {"name": name, "ph": "X", "ts": 0.0, "dur": dur, "pid": 1, "tid": 2,
          "args": {"step": step, **args}}
    if tdur is not None:
        ev["tdur"] = tdur
    return ev


def _batch(step, scale=1.0):
    """One batch's scheduler-lane spans, durations in us."""
    return [_x("sample", 300e3 * scale, 280e3 * scale, step),
            _x("negatives", 100e3 * scale, 70e3 * scale, step),
            _x("schedule", 80e3 * scale, 78e3 * scale, step, n=512),
            _x("transfer", 20e3 * scale, 12e3 * scale, step,
               bytes=int(270_000 * scale)),
            _x("prepared_put", 2e3 * scale, 1e3 * scale, step)]


@pytest.fixture
def read():
    return {n: harness.load_module("metrics", n).read for n in NAMES}


def test_readers_on_synthetic_spans(read):
    ctx = _ctx(_batch(0) + _batch(1, scale=2.0)
               + [_x("dispatch", 50.0, 40.0, 0)])
    assert read["train.negatives_ms"](ctx) == pytest.approx(150.0)
    # off-CPU of sample/negatives/schedule/transfer: 20+30+2+8 = 60 ms in
    # batch 0, 120 ms in batch 1; prepared_put and dispatch are not work
    assert read["train.scheduler_offcpu_ms"](ctx) == pytest.approx(90.0)
    assert read["train.queue_full_ms"](ctx) == pytest.approx(3.0)
    assert read["train.h2d_kb"](ctx) == pytest.approx(405_000 / 1024)


@pytest.mark.parametrize("name", NAMES)
def test_readers_find_nothing_without_their_spans(read, name):
    assert read[name](_ctx([])) is None
    # what a program without these spans records: sample, schedule and
    # transfer with neither tdur nor bytes
    old = [_x("sample", 3e5, step=s) for s in (0, 1)] + [
        _x("schedule", 8e4, step=s, n=512) for s in (0, 1)] + [
        _x("transfer", 2e4, step=s, n_steps=8) for s in (0, 1)]
    assert read[name](_ctx(old)) is None


def test_offcpu_needs_tdur_on_every_work_span(read):
    spans = _batch(0)
    del spans[1]["tdur"]
    assert read["train.scheduler_offcpu_ms"](_ctx(spans)) is None


def test_h2d_needs_bytes_on_every_transfer(read):
    spans = _batch(0) + _batch(1)
    del spans[8]["args"]["bytes"]
    assert spans[8]["name"] == "transfer"
    assert read["train.h2d_kb"](_ctx(spans)) is None
