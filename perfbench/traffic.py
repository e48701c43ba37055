"""The one traffic generator: reads a mix's parameters (a JSON file under
``perfbench/traffic/``) and draws its requests and fine-tune rows from the
seed.

Every seed gets the same work in another order: lengths are the quantiles
of their lognormal at evenly spaced probabilities, Poisson gaps the
quantiles of their exponential, and adapters the Zipf shares rounded to
whole requests; the seed permutes each list and draws the token ids.  So
two seeds differ in order and content, not in how much work they bring.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def lognormal_grid(n: int, spec: Dict) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def exponential_grid(n: int, rate: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """Requests per adapter rank for ``n`` requests over ``k`` adapters."""
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    c = np.floor(p * n).astype(np.int64)
    rest = np.argsort(-(p * n - c))[: n - c.sum()]
    c[rest] += 1
    return c


def make_requests(mix: Dict, vocab: int, rate: float, horizon_s: float,
                  seed: int) -> List[Dict]:
    """Open-loop requests for ``horizon_s`` seconds at ``rate`` per second:
    dicts with ``rid``, ``arrival`` (seconds from the start of load),
    ``adapter`` (index), ``prompt`` (int32 tokens: the adapter's system
    prompt, then the user turn) and ``max_new``."""
    rng = np.random.default_rng([int(seed), 11])
    n = max(1, math.ceil(rate * horizon_s))
    k = mix["adapters"]
    system = rng.integers(0, vocab, (k, mix["system_prompt_tokens"]),
                          dtype=np.int64).astype(np.int32)
    arrivals = np.cumsum(rng.permutation(exponential_grid(n, rate)))
    adapters = rng.permutation(np.repeat(np.arange(k),
                                         zipf_counts(n, k, mix["zipf_s"])))
    users = rng.permutation(lognormal_grid(n, mix["user_tokens"]))
    outs = rng.permutation(lognormal_grid(n, mix["output_tokens"]))
    reqs = []
    for i in range(n):
        user = rng.integers(0, vocab, users[i], dtype=np.int64)
        reqs.append(dict(rid=i, arrival=float(arrivals[i]),
                         adapter=int(adapters[i]),
                         prompt=np.concatenate([system[adapters[i]],
                                                user.astype(np.int32)]),
                         max_new=int(outs[i])))
    return reqs


def make_train_rows(mix: Dict, vocab: int, seed: int) -> List[np.ndarray]:
    """The trainer's instruction rows (token ids; labels are the tokens)."""
    spec = mix["trainer"]
    rng = np.random.default_rng([int(seed), 12])
    lens = rng.permutation(lognormal_grid(spec["rows"], spec["tokens"]))
    return [rng.integers(0, vocab, L, dtype=np.int64).astype(np.int32)
            for L in lens]
