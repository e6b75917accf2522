"""The `train.collective_kb` reader on a hand-built ``Context``: the mean
wire bytes of the window's dispatches, and None where a dispatch lacks the
arg (one device, or a program that does not read its collectives)."""
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (puts the repository on sys.path)
from bench import harness, layers

read = harness.load_module("metrics", "train.collective_kb").read


def _ctx(spans):
    run = SimpleNamespace(args=SimpleNamespace(seconds=20.0),
                          window_info={"steps": 2},
                          devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    return layers.Context(run, spans, None)


def _dispatch(step, **args):
    return {"name": "dispatch", "ph": "X", "ts": 0.0, "dur": 50.0, "pid": 1,
            "tid": 1, "args": {"step": step, **args}}


def test_mean_wire_kb_of_the_dispatches():
    spans = [_dispatch(0, collective_wire_bytes=3 * 1024 ** 2,
                       collective_payload_bytes=1),
             _dispatch(1, collective_wire_bytes=1024 ** 2),
             {"name": "transfer", "ph": "X", "ts": 0.0, "dur": 5.0,
              "args": {"collective_wire_bytes": 10 ** 9}}]
    assert read(_ctx(spans)) == pytest.approx(2 * 1024)


@pytest.mark.parametrize("spans", [
    [], [_dispatch(0), _dispatch(1)],
    [_dispatch(0, collective_wire_bytes=1024), _dispatch(1)]],
    ids=["no dispatch", "one device", "one dispatch without"])
def test_none_without_the_arg(spans):
    assert read(_ctx(spans)) is None
