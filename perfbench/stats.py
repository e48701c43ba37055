"""The benchmark's arithmetic: latency percentiles over the window, the
paper's SLO, queue growth and the table of peaks (a step's model FLOPs are
its architecture's: ``arch/<model_type>.py``).

``SLO`` and ``meets_slo`` copy ``serving/slo.py`` (the paper's App. C/D
limits) so that the yardstick does not move with the program.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class SLO:
    max_waiting_s: float = 6.0
    mean_decode_ms: float = 200.0
    max_decode_ms: float = 1000.0


@dataclasses.dataclass
class Served:
    """One request as the benchmark saw it: scheduled arrival, and the times
    its tokens were emitted (engine clock, seconds)."""
    arrival: float
    token_times: List[float]
    failed: bool = False


def due(reqs: Iterable[Served], w0: float, w1: float) -> List[Served]:
    return [r for r in reqs if w0 <= r.arrival < w1]


def ttfts(reqs: Sequence[Served], w1: float) -> np.ndarray:
    """Time to first token of each request; one that has none by ``w1``
    counts with its wait so far, so a stall cannot hide."""
    out = []
    for r in reqs:
        t = r.token_times[0] if r.token_times and r.token_times[0] <= w1 \
            else w1
        out.append(t - r.arrival)
    return np.asarray(out, np.float64)


def token_gaps(reqs: Iterable[Served], w0: float, w1: float) -> np.ndarray:
    """Every gap between consecutive output tokens that ends in the
    window."""
    out = []
    for r in reqs:
        t = np.asarray(r.token_times, np.float64)
        if len(t) < 2:
            continue
        g, end = np.diff(t), t[1:]
        out.append(g[(end >= w0) & (end <= w1)])
    return np.concatenate(out) if out else np.zeros((0,))


def percentile(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(x, q)) if len(x) else None


def meets_slo(r: Served, w1: float, slo: SLO = SLO()) -> bool:
    """The paper's SLO on what is known at ``w1``: a request still waiting
    fails once its wait passes the limit; a request still decoding is judged
    on the gaps it has so far."""
    if r.failed:
        return False
    t = [x for x in r.token_times if x <= w1]
    wait = (t[0] if t else w1) - r.arrival
    if wait > slo.max_waiting_s:
        return False
    if len(t) >= 2:
        g = np.diff(np.asarray(t)) * 1e3
        if g.mean() > slo.mean_decode_ms or g.max() > slo.max_decode_ms:
            return False
    return True


def queue_growth(samples: Sequence[Tuple[float, int]]) -> float:
    """Least-squares slope of the waiting queue over the window, times the
    window: how many requests the queue gained."""
    if len(samples) < 2:
        return 0.0
    t = np.array([s[0] for s in samples])
    q = np.array([s[1] for s in samples], np.float64)
    if t[-1] <= t[0]:
        return 0.0
    slope = np.polyfit(t - t[0], q, 1)[0]
    return float(slope * (t[-1] - t[0]))


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; a chip not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
