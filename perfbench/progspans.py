#!/usr/bin/env python3
"""The program's own spans (``repro.spans``) on a profiler trace's host
plane, for the metric readers: the engine's span times and counts per
tick that ran a step, and the device's idle gaps labelled down to the
innermost program span.

The engine records its spans while a profiler trace collects host
events, so a ``--trace 1`` run's trace holds the window's ticks; each
span's attributes are its event's stats.  A program without the recorder
leaves no ``serve.*`` event there: every reader of them reads nothing,
and its metric is left out.

    python3 perfbench/progspans.py [trace directory]

prints ``reduce`` of the newest trace as one JSON object (default
directory: the harness's ``.trace``).
"""
from __future__ import annotations

import bisect
import functools
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from perfbench import trace  # noqa: E402

PROGRAM = "serve."                    # prefix of the engine's span names
TICK = "serve.tick"                   # one per tick; ``kind`` where it steps
COLLECTIONS = "host.gc"
COMPILES = ("backend_compile", "backend_compile_and_load")   # JAX's own
ENGINE_HOST = ("serve.plan", "serve.scatter")   # the engine's host work

Interval = Tuple[float, float]
Span = Tuple[float, float, str, Dict]   # start ns, end ns, name, stats


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def innermost(t: float, spans: Sequence[Span]) -> Optional[str]:
    """The name of the latest-opened span covering ``t``."""
    best = None
    for a, b, n, _ in spans:
        if a <= t < b and (best is None or a >= best[0]):
            best = (a, n)
    return best[1] if best else None


def label(t: float, harness: Dict[str, List[Interval]],
          program: Sequence[Span], host: Sequence[Span]) -> str:
    """``trace.label``, then `` > `` the innermost program span covering
    ``t``, then `` > `` a compile or collection covering it."""
    out = [trace.label(t, harness)]
    for group in (program, host):
        n = innermost(t, group)
        if n:
            out.append(n)
    return " > ".join(out)


def stepped(program: Sequence[Span]) -> Dict:
    """The program's spans inside the ticks that ran a step: ``ticks``,
    their number; ``span_ms``, each span name's milliseconds per such
    tick; ``attrs``, each ``<span>:<attribute>`` summed over them."""
    ticks = sorted((a, b) for a, b, n, st in program
                   if n == TICK and "kind" in st)
    starts = [a for a, _ in ticks]
    span_s: Dict[str, float] = {}
    attrs: Dict[str, float] = {}
    for a, b, n, st in program:
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= ticks[i][1]:
            continue
        span_s[n] = span_s.get(n, 0.0) + (b - a) * 1e-9
        for k, v in st.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                attrs[f"{n}:{k}"] = attrs.get(f"{n}:{k}", 0) + v
    return {"ticks": len(ticks),
            "span_ms": {n: 1e3 * s / len(ticks) for n, s in span_s.items()},
            "attrs": attrs}


def walk(pd) -> Tuple[Dict[str, List[Interval]], List[Span], List[Span],
                      List[Interval]]:
    """The trace's harness spans by name, program spans, compiles and
    collections, and device operations."""
    harness: Dict[str, List[Interval]] = {n: [] for n in trace.SPANS}
    program: List[Span] = []
    host: List[Span] = []
    devices: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    n = ev.name
                    if n in harness:
                        harness[n].append((ev.start_ns, ev.end_ns))
                    elif n.startswith(PROGRAM):
                        program.append((ev.start_ns, ev.end_ns, n,
                                        dict(ev.stats)))
                    elif n == COLLECTIONS or n in COMPILES:
                        host.append((ev.start_ns, ev.end_ns, n, {}))
        elif trace.DEVICE.match(plane.name):
            devices += [(ev.start_ns, ev.end_ns) for line in plane.lines
                        if line.name == trace.OPS_LINE for ev in line.events]
    return harness, program, host, devices


def reduce(pd) -> Dict:
    """``idle_gaps`` as ``trace.reduce`` finds them, labelled down to the
    program's spans; ``idle_host_s``, the device-idle time in the window
    under ``ENGINE_HOST`` spans (None where the trace holds none);
    ``window_s``; and ``stepped`` of the program's spans."""
    harness, program, host, devices = walk(pd)
    if not devices:
        raise ValueError("the trace holds no device operations")
    ticks = harness["engine.tick"] or devices
    lo, hi = min(a for a, _ in ticks), max(b for _, b in ticks)
    idle = trace.gaps(trace.union(trace.clip(devices, lo, hi)), lo, hi)
    top = sorted(idle, key=lambda g: g[0] - g[1])[:trace.TOP]
    work = trace.union([(a, b) for a, b, n, _ in program
                        if n in ENGINE_HOST])
    return {
        "idle_gaps": [[label((a + b) / 2, harness, program, host),
                       (b - a) * 1e-9] for a, b in top],
        "idle_host_s": overlap(idle, work) * 1e-9 if work else None,
        "window_s": (hi - lo) * 1e-9,
        "stepped": stepped(program),
    }


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> Dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def reduce_dir(root: Path) -> Dict:
    """``reduce`` of the newest ``.xplane.pb`` under ``root`` (read once
    for all the readers of one run)."""
    files = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return _reduce_file(str(files[-1]), files[-1].stat().st_mtime_ns)


def window(run) -> Optional[Dict]:
    """``stepped`` of a traced run's trace; None for an untraced run or a
    trace that holds no tick that stepped."""
    if not run.trace:
        return None
    from perfbench import harness
    got = reduce_dir(harness.TRACE_DIR)["stepped"]
    return got if got["ticks"] else None


if __name__ == "__main__":
    where = (Path(sys.argv[1]) if len(sys.argv) > 1
             else Path(__file__).resolve().parent / ".trace")
    print(json.dumps(reduce_dir(where)))
