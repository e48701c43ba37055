"""The benchmark's arithmetic: latency percentiles over the window, the
paper's SLO, queue growth, model FLOPs of a step, and the table of peaks.

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


# -- model FLOPs --------------------------------------------------------------

def layer_params(spec: Dict) -> int:
    """Parameters of one decoder layer's linears."""
    d, h, g = (spec["hidden_size"], spec["num_attention_heads"],
               spec["num_key_value_heads"])
    hd, ff = d // h, spec["intermediate_size"]
    return d * h * hd * 2 + d * g * hd * 2 + 3 * d * ff


def lora_params(spec: Dict, r: int) -> int:
    """Parameters of one layer's rank-``r`` adapter on the seven targets."""
    d, h, g = (spec["hidden_size"], spec["num_attention_heads"],
               spec["num_key_value_heads"])
    hd, ff = d // h, spec["intermediate_size"]
    dims = [(d, h * hd), (d, g * hd), (d, g * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]
    return sum(r * (a + b) for a, b in dims)


def step_flops(spec: Dict, r: int, ft_lens: Sequence[int],
               pf_rows: Sequence[Tuple[int, int]],
               dec_ctx: Sequence[int]) -> float:
    """Model FLOPs one unified step needs: 2 per parameter per token through
    the layers (base and adapter), 4 * heads * head_dim per attended key per
    layer, and the head where logits are taken (every fine-tune position,
    the last prompt position of a prefill row, each decode row).  Fine-tune
    tokens count twice: the forward and the input gradient.  ``pf_rows``
    holds (computed tokens, cached prefix); ``dec_ctx`` each decode row's
    context including its new token."""
    L, d, V = (spec["num_hidden_layers"], spec["hidden_size"],
               spec["vocab_size"])
    h = spec["num_attention_heads"]
    lin = 2.0 * (layer_params(spec) + lora_params(spec, r))
    att = 4.0 * h * (d // h)
    head = 2.0 * d * V

    def fwd(tokens, keys, logit_rows):
        return L * (lin * tokens + att * keys) + head * logit_rows

    total = 0.0
    for n in ft_lens:
        total += 2 * fwd(n, n * (n + 1) / 2, n)
    for n, c in pf_rows:
        total += fwd(n, n * c + n * (n + 1) / 2, 1)
    for c in dec_ctx:
        total += fwd(1, c, 1)
    return total


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks; a chip not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
