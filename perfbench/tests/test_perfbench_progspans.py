"""The program's spans for the benchmark (``perfbench/progspans.py``) and
the readers of the metrics they feed: idle gaps labelled down to the
innermost program span and the idle time under the engine's host work, on
hand-made planes and on the recorded v5e trace (which holds no program
spans); the engine's span times and counts per stepped tick, and the
readers of them, on hand-made planes and on the recorder's spans in a CPU
profiler trace; and nothing read where the program has no recorder."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from perfbench import harness, progspans, trace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, a, b, **stats):
    return NS(name=name, start_ns=a, end_ns=b, stats=stats.items())


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def traced():
    """Two ticks: the first plans, dispatches a step whose call compiles,
    waits, scatters (reading logits back); the second plans through a
    collection and steps."""
    host = plane("/host:CPU", [("python", [
        ev("engine.tick", 0, 100),
        ev("serve.tick", 0, 100), ev("serve.plan", 0, 20),
        ev("step.fwd", 20, 80), ev("serve.dispatch", 20, 50),
        ev("backend_compile_and_load", 22, 45), ev("serve.wait", 50, 80),
        ev("serve.scatter", 80, 98), ev("serve.readback", 82, 90),
        ev("engine.tick", 110, 200),
        ev("serve.tick", 110, 200), ev("serve.plan", 110, 150),
        ev("host.gc", 115, 135), ev("step.grad", 150, 190),
        ev("serve.dispatch", 150, 155), ev("serve.wait", 155, 190),
        ev("serve.scatter", 190, 199)])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("fusion.1", 48, 78), ev("copy.2", 82, 90),
                     ev("fusion.1", 156, 188)])])
    return NS(planes=[host, dev])


def test_labels_reach_the_innermost_program_span():
    red = progspans.reduce(traced())
    gaps = dict((round(s * 1e9), n) for n, s in red["idle_gaps"])
    assert gaps == {
        48: "step.fwd > serve.dispatch > backend_compile_and_load",
        4: "engine.tick (after step) > serve.scatter",
        66: "engine.tick (before step) > serve.plan > host.gc",
        12: "engine.tick (after step) > serve.scatter",
    }


def test_idle_under_the_engines_host_work():
    red = progspans.reduce(traced())
    # idle 0-48, 78-82, 90-156, 188-200 under plan or scatter: 0-20,
    # 80-82, 90-98, 110-150, 190-199
    assert red["idle_host_s"] == pytest.approx((20 + 2 + 8 + 40 + 9) * 1e-9)
    assert red["window_s"] == pytest.approx(200e-9)
    idle = sum(s for _, s in trace.reduce(traced())["idle_gaps"])
    assert red["idle_host_s"] <= idle


def test_labels_unchanged_without_program_spans():
    host = plane("/host:CPU", [("python", [
        ev("engine.tick", 0, 100), ev("step.fwd", 20, 80),
        ev("engine.tick", 110, 200), ev("step.grad", 120, 190)])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("fusion.1", 25, 60), ev("dot.2", 60, 75),
                     ev("fusion.1", 125, 185), ev("fusion.1", 300, 400)])])
    pd = NS(planes=[host, dev])
    red = progspans.reduce(pd)
    assert red["idle_gaps"] == trace.reduce(pd)["idle_gaps"]
    assert red["idle_host_s"] is None


def test_recorded_chip_trace_unchanged():
    red = trace.reduce_dir(DATA)
    assert red["busy_s"] == pytest.approx(8.2624e-05)
    assert red["window_s"] == pytest.approx(0.015292568)
    assert [s for _, s in red["device_ops"]] == pytest.approx(
        [7.4853e-05, 3.123e-06, 2.751e-06, 1.653e-06, 1.8e-08])
    assert red["device_ops"][0][0].startswith("%convolution_tanh_fusion.2")
    want = [("engine.tick (after step)", 0.005445376),
            ("engine.tick (after step)", 0.005430721),
            ("step.fwd", 0.003429582),
            ("engine.tick (before step)", 0.000904255)] + [
        ("engine.tick (before step)", s) for s in (2e-9,) * 4 + (1e-9,) * 2]
    assert [n for n, _ in red["idle_gaps"]] == [n for n, _ in want]
    assert [s for _, s in red["idle_gaps"]] == pytest.approx(
        [s for _, s in want])
    prog = progspans.reduce_dir(DATA)
    assert prog["idle_gaps"] == red["idle_gaps"]
    assert prog["idle_host_s"] is None
    assert prog["window_s"] == pytest.approx(red["window_s"])


MS = 1_000_000    # ns


def recorded():
    """Three ticks on the trace: a forward step, an idle tick and a grad
    step, each stepped tick with its spans' attributes as stats."""
    host = plane("/host:CPU", [("python", [
        ev("engine.tick", 0, 101 * MS),
        ev("serve.tick", 0, 100 * MS, kind="fwd", kv_blocks_used=6,
           kv_blocks_reserved=8),
        ev("serve.plan", 0, 4 * MS),
        ev("serve.admit", 1 * MS, 2 * MS, rid="[7]", admissions=1,
           queue_wait_s=0.5),
        ev("serve.assemble", 2 * MS, 3 * MS, step_tokens=30,
           step_positions=40),
        ev("serve.dispatch", 4 * MS, 6 * MS, kind="fwd"),
        ev("serve.wait", 6 * MS, 90 * MS),
        ev("serve.scatter", 90 * MS, 100 * MS),
        ev("serve.readback", 91 * MS, 93 * MS, what="dec_logits"),
        ev("serve.readback", 95 * MS, 96 * MS, what="ft_loss"),
        ev("serve.tick", 100 * MS, 101 * MS),                 # no step
        ev("serve.plan", 100 * MS, 101 * MS),
        ev("host.gc", 101 * MS, 102 * MS, collected=3),
        ev("engine.tick", 200 * MS, 300 * MS),
        ev("serve.tick", 200 * MS, 300 * MS, kind="grad", kv_blocks_used=2,
           kv_blocks_reserved=2),
        ev("serve.plan", 200 * MS, 208 * MS),
        ev("serve.admit", 201 * MS, 202 * MS, rid="[8, 9]", admissions=1,
           queue_wait_s=0.25),
        ev("serve.assemble", 203 * MS, 204 * MS, step_tokens=10,
           step_positions=40),
        ev("serve.dispatch", 208 * MS, 212 * MS, kind="grad"),
        ev("serve.wait", 212 * MS, 290 * MS),
        ev("serve.scatter", 290 * MS, 300 * MS),
        ev("serve.readback", 291 * MS, 292 * MS)])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("fusion.1", 6 * MS, 90 * MS),
                     ev("fusion.2", 212 * MS, 290 * MS)])])
    return NS(planes=[host, dev])


def test_stepped_ticks_of_the_program_spans():
    got = progspans.reduce(recorded())["stepped"]
    assert got["ticks"] == 2
    assert got["span_ms"]["serve.tick"] == pytest.approx(100.0)
    assert got["span_ms"]["serve.readback"] == pytest.approx(2.0)
    assert "host.gc" not in got["span_ms"]
    assert got["attrs"] == pytest.approx({
        "serve.tick:kv_blocks_used": 8, "serve.tick:kv_blocks_reserved": 10,
        "serve.admit:admissions": 2, "serve.admit:queue_wait_s": 0.75,
        "serve.assemble:step_tokens": 40,
        "serve.assemble:step_positions": 80})


@pytest.mark.parametrize("name,want", [
    ("span_ms.plan.coserve", 6.0), ("span_ms.dispatch.saturate", 3.0),
    ("span_ms.readback.coserve", 2.0), ("queue_wait_ms.saturate", 375.0),
    ("pad_share.coserve", 50.0), ("kv_use_share.saturate", 80.0),
    # idle 0-6, 90-212 and 290-300 ms; under plan or scatter 0-4, 90-100,
    # 100-101, 200-208 and 290-300 ms, of a 300 ms window
    ("idle_host_share.coserve", 100.0 * 33 / 300)])
def test_readers_on_records(monkeypatch, name, want):
    red = progspans.reduce(recorded())
    monkeypatch.setattr(progspans, "reduce_dir", lambda root: red)
    run = NS(trace={"window_s": red["window_s"]})
    assert harness.reader(name)(run, name) == pytest.approx(want)


def test_readers_find_nothing_without_a_recorder(monkeypatch):
    # the recorded chip trace comes from a program without the recorder
    run = NS(trace={"window_s": 1.0})
    monkeypatch.setattr(harness, "TRACE_DIR", DATA)
    for name in ("span_ms.plan.coserve", "span_ms.dispatch.saturate",
                 "span_ms.readback.coserve", "queue_wait_ms.coserve",
                 "pad_share.saturate", "kv_use_share.coserve",
                 "idle_host_share.saturate"):
        assert harness.reader(name)(run, name) is None, name
        assert harness.reader(name)(NS(trace=None), name) is None, name


def test_records_of_the_program_recorder(tmp_path):
    # the recorder's spans reach a profiler trace's host plane, with their
    # attributes as stats, and reduce to the tick's numbers
    import jax
    from jax.profiler import ProfileData
    from repro import spans
    spans.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans.follow_profiler()
        with spans.span("serve.tick") as tick:
            with spans.span("serve.plan"):
                with spans.span("serve.admit", rid=[4, 5]) as admit:
                    admit.set(admissions=2, queue_wait_s=0.5)
            tick.set(kind="fwd", kv_blocks_used=3, kv_blocks_reserved=4)
        with spans.span("serve.tick"):
            pass
    finally:
        jax.profiler.stop_trace()
        spans.follow_profiler()
    assert spans.span("serve.tick") is spans.OFF
    assert spans.drain() == []          # only enable() keeps records
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    _, program, _, _ = progspans.walk(pd)
    assert [n for _, _, n, _ in program] == [
        "serve.tick", "serve.plan", "serve.admit", "serve.tick"]
    got = progspans.stepped(program)
    assert got["ticks"] == 1
    assert set(got["span_ms"]) == {"serve.tick", "serve.plan",
                                   "serve.admit"}
    assert got["attrs"] == pytest.approx({
        "serve.admit:admissions": 2, "serve.admit:queue_wait_s": 0.5,
        "serve.tick:kv_blocks_used": 3, "serve.tick:kv_blocks_reserved": 4})
