"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the device's busy time, the window, the
device operations that took most time, and the longest idle gaps labelled
by what the host was doing.

Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops`` line
holds one event per operation.  Host spans are the harness's own
``TraceAnnotation`` events (``engine.tick``, ``step.fwd``, ``step.grad``,
``step.apply``) on the ``/host:CPU`` plane.  Both are on one clock.  The
window runs from the first ``engine.tick`` to the end of the last.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

SPANS = ("engine.tick", "step.fwd", "step.grad", "step.apply")
DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# ops that only contain other ops: their time is their body's
CONTAINERS = re.compile(r"^\S+ = .*? (while|conditional|call)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
NAME_WIDTH = 120   # characters of an op's HLO text kept as its name
TOP = 10           # entries in each list of the breakdown


def op_name(hlo: str) -> str:
    """An op's HLO text without layouts, cut to ``NAME_WIDTH`` characters."""
    return LAYOUT.sub("", hlo)[:NAME_WIDTH]

Interval = Tuple[float, float]


def union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def label(t: float, spans: Dict[str, List[Interval]]) -> str:
    """What the host was doing at ``t``: inside a step call, in a tick
    before or after its step, or between ticks."""
    for name in ("step.fwd", "step.grad", "step.apply"):
        if any(a <= t < b for a, b in spans[name]):
            return name
    for a, b in spans["engine.tick"]:
        if a <= t < b:
            inner = [s for n in ("step.fwd", "step.grad") for s in spans[n]
                     if a <= s[0] < b]
            if not inner:
                return "engine.tick (no step)"
            return ("engine.tick (before step)" if t < min(inner)[0]
                    else "engine.tick (after step)")
    return "between ticks"


def reduce(pd) -> Dict:
    """``busy_s`` (union of device op time in the window, averaged over the
    device planes), ``window_s``, ``device_ops`` ([name, seconds] of the
    ``TOP`` operations by total time) and ``idle_gaps`` ([label, seconds]
    of the ``TOP`` longest gaps in the union of all devices' ops)."""
    spans: Dict[str, List[Interval]] = {n: [] for n in SPANS}
    devices: List[List[Interval]] = []
    ops: Dict[str, float] = {}
    op_events = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
        elif DEVICE.match(plane.name):
            iv = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    iv.append((ev.start_ns, ev.end_ns))
                    if not CONTAINERS.match(ev.name):
                        op_events.append((op_name(ev.name), ev.start_ns,
                                          ev.end_ns))
            devices.append(iv)
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operations")
    ticks = spans["engine.tick"]
    lo = min(a for a, _ in ticks) if ticks else min(
        a for d in devices for a, _ in d)
    hi = max(b for _, b in ticks) if ticks else max(
        b for d in devices for _, b in d)
    for name, a, b in op_events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
    busy = [sum(b - a for a, b in union(clip(d, lo, hi))) for d in devices]
    all_busy = union(clip([x for d in devices for x in d], lo, hi))
    idle = sorted(gaps(all_busy, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label((a + b) / 2, spans), (b - a) * 1e-9]
                      for a, b in idle],
    }


def reduce_dir(root: Path) -> Dict:
    """Reduce the newest ``.xplane.pb`` under ``root``."""
    from jax.profiler import ProfileData
    files = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return reduce(ProfileData.from_file(str(files[-1])))
