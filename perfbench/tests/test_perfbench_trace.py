"""The reduction from a profiler trace to busy time, idle gaps and top
operations: on hand-made planes, and on a small trace recorded on a TPU v5e
(three ``engine.tick`` spans, each around a ``step.fwd`` of a jitted
four-step matmul loop, with host sleeps between)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from perfbench import trace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, a, b):
    return NS(name=name, start_ns=a, end_ns=b)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def test_union_and_gaps():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.gaps(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert trace.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_reduce_hand_made():
    host = plane("/host:CPU", [("python", [
        ev("engine.tick", 0, 100), ev("step.fwd", 20, 80),
        ev("engine.tick", 110, 200), ev("step.grad", 120, 190)])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("fusion.1", 25, 60), ev("dot.2", 60, 75),
                     ev("fusion.1", 125, 185), ev("fusion.1", 300, 400)]),
        ("XLA Modules", [ev("jit_step", 25, 75)])])
    red = trace.reduce(NS(planes=[host, dev]))
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(110e-9)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(95e-9)]
    gaps = dict((round(s * 1e9), n) for n, s in red["idle_gaps"])
    assert gaps == {25: "engine.tick (before step)", 50: "between ticks",
                    15: "engine.tick (after step)"}


def test_reduce_needs_device_ops():
    host = plane("/host:CPU", [("python", [ev("engine.tick", 0, 10)])])
    with pytest.raises(ValueError):
        trace.reduce(NS(planes=[host]))


def test_reduce_recorded_chip_trace():
    red = trace.reduce_dir(DATA)
    # three steps of about 27 us on the device, in 15 ms of ticks and host
    # sleeps
    assert 5e-5 < red["busy_s"] < 2e-4
    assert 0.01 < red["window_s"] < 0.03
    assert red["device_ops"][0][0].startswith("%convolution_tanh_fusion")
    assert all(s > 0 for _, s in red["device_ops"])
    assert not any("while(" in n for n, _ in red["device_ops"])
    labels = [n for n, _ in red["idle_gaps"]]
    assert labels[0] == "engine.tick (after step)"
    assert set(labels) <= {"engine.tick (before step)", "step.fwd",
                           "engine.tick (after step)", "between ticks"}
