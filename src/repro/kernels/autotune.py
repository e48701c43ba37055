"""Tile/split autotuning for the paged-attention kernel family.

The sequential kernels hard-coded their tiles (``block_k = 512`` for the
linear-cache decode kernel, one pool block per grid step for the paged
family) and the split-K kernels need a ``num_splits``.  This module owns
that choice, per shape key ``(head_dim, block_size, nbt, bh)`` where ``bh =
B * n_kv_heads`` is the batch-parallelism the grid already has (the paged
kernels run one grid cell per request and KV head, each scoring its whole
GQA query group):

* a tuning TABLE — in-memory dict, loadable from / savable to a small JSON
  file — populated by a ``benchmarks/bench_kernels.py``-driven sweep
  (wall-clock ``measure`` on real TPU, the occupancy model below in
  interpret/CPU mode);
* a deterministic HEURISTIC fallback for any shape the table misses, so CI
  and cold starts never depend on a tuning run having happened.

The occupancy model: a device runs ``lanes`` grid cells concurrently
(GPU SMs / TPU megacore+DMA pipelining; calibrate per device with the
``REPRO_ATTN_LANES`` env var — validated, garbage fails loudly).  The
sequential walk costs ``ceil(bh / lanes) * nbt`` block-tile visits; a
``ns``-way split costs ``ceil(bh * ns / lanes) * ceil(nbt / ns)`` plus a
small LSE-merge epilogue.  Splitting wins exactly when ``bh`` alone cannot
fill the lanes — long context, small batch — and is useless (ns = 1) once
``bh >= lanes``, which is also what flash-decoding observes on real
hardware.

Every table mutation bumps ``table_version()``; the jit step caches in
``core.unified`` key on it, so loading a tuning table mid-process can never
hit a stale trace that baked in the old choice.
"""
from __future__ import annotations

import json
import os
from typing import Callable, Dict, Iterable, NamedTuple, Optional, Tuple

# modeled concurrent grid cells (default); calibrate per device with the
# REPRO_ATTN_LANES env override — see effective_lanes()
LANES = 16
ENV_LANES = "REPRO_ATTN_LANES"
SPLIT_CANDIDATES = (1, 2, 4, 8, 16)
# below this many blocks per split the per-split fixed costs (q load, merge
# traffic) dominate — don't shard a walk that short
MIN_BLOCKS_PER_SPLIT = 4
_MERGE_FIXED = 1.0          # merge epilogue launch, in block-tile-visit units
_MERGE_PER_SPLIT = 0.25     # per-partial merge traffic, same units

ShapeKey = Tuple[int, int, int, int]       # (head_dim, block_size, nbt, bh)


class AttnConfig(NamedTuple):
    """One kernel-family tuning decision for a shape key."""
    block_k: int             # KV tile of the linear-cache decode kernel;
    #                          the paged kernels stream one pool block per
    #                          step, so there it only documents the tile
    num_splits: int          # split-K fan-out (1 = sequential walk)


_TABLE: Dict[ShapeKey, AttnConfig] = {}
_VERSION = 0
_ENV_LOADED = False

ENV_TUNE_FILE = "REPRO_ATTN_TUNE_FILE"


def table_version() -> int:
    """Monotone counter bumped on every table mutation — step-compile
    caches key on it (see core.unified)."""
    return _VERSION


def effective_lanes() -> int:
    """The occupancy model's concurrent-grid-cell count: the
    ``REPRO_ATTN_LANES`` env override when set (per-device calibration
    without editing source — a TPU v5e megacore pipelines differently from
    an H100's SM count), else the ``LANES`` default.  Garbage values fail
    LOUDLY: a typo silently falling back to 16 would bake the wrong
    fan-outs into every heuristic choice on that host."""
    raw = os.environ.get(ENV_LANES, "").strip()
    if not raw:
        return LANES
    try:
        lanes = int(raw)
    except ValueError:
        raise ValueError(
            f"{ENV_LANES}={raw!r} is not an integer; set the modeled "
            f"concurrent grid-cell count (e.g. 16), or unset it for the "
            f"default {LANES}") from None
    if lanes < 1:
        raise ValueError(f"{ENV_LANES}={raw!r} must be >= 1")
    return lanes


def put_config(key: ShapeKey, cfg: AttnConfig) -> None:
    global _VERSION
    _TABLE[tuple(int(k) for k in key)] = AttnConfig(int(cfg[0]), int(cfg[1]))
    _VERSION += 1


def clear_table() -> None:
    global _VERSION
    _TABLE.clear()
    _VERSION += 1


def get_config(key: ShapeKey) -> Optional[AttnConfig]:
    return _TABLE.get(tuple(int(k) for k in key))


def modeled_grid_time(bh: int, nbt: int, num_splits: int,
                      lanes: Optional[int] = None) -> float:
    """Occupancy-model cost (in block-tile visits) of one attention launch:
    waves of ``lanes`` concurrent cells, each cell walking its share of the
    table, plus the LSE-merge epilogue when split."""
    lanes = effective_lanes() if lanes is None else lanes
    ns = max(1, int(num_splits))
    npb = -(-nbt // ns)
    waves = -(-bh * ns // lanes)
    t = float(waves * npb)
    if ns > 1:
        t += _MERGE_FIXED + _MERGE_PER_SPLIT * ns * (-(-bh // lanes))
    return t


def candidate_splits(nbt: int) -> Tuple[int, ...]:
    """Split counts worth trying for a table of ``nbt`` blocks."""
    return tuple(ns for ns in SPLIT_CANDIDATES
                 if ns == 1 or -(-nbt // ns) >= MIN_BLOCKS_PER_SPLIT)


def default_block_k(head_dim: int) -> int:
    """Linear-cache decode KV tile: fill roughly one VMEM-friendly
    [block_k, head_dim] strip."""
    return 512 if head_dim <= 64 else 256


def heuristic(head_dim: int, block_size: int, nbt: int, bh: int,
              lanes: Optional[int] = None) -> AttnConfig:
    """Deterministic fallback: minimize the occupancy model over the
    candidate splits (ties -> fewer splits, less merge traffic).  ``lanes``
    defaults to ``effective_lanes()`` — the REPRO_ATTN_LANES per-device
    calibration reaches every heuristic choice through here."""
    lanes = effective_lanes() if lanes is None else lanes
    best, best_t = 1, modeled_grid_time(bh, nbt, 1, lanes)
    for ns in candidate_splits(nbt):
        t = modeled_grid_time(bh, nbt, ns, lanes)
        if t < best_t:
            best, best_t = ns, t
    return AttnConfig(default_block_k(head_dim), best)


def choose(head_dim: int, block_size: int, nbt: int, bh: int) -> AttnConfig:
    """Table lookup with heuristic fallback — the one entry point the model
    calls at trace time."""
    _maybe_load_env()
    got = get_config((head_dim, block_size, nbt, bh))
    return got if got is not None else heuristic(head_dim, block_size,
                                                 nbt, bh)


def _maybe_load_env() -> None:
    global _ENV_LOADED
    if _ENV_LOADED:
        return
    _ENV_LOADED = True
    path = os.environ.get(ENV_TUNE_FILE, "").strip()
    if path:
        load_table(path)


# ------------------------------------------------------------- persistence

def save_table(path: str) -> int:
    """Write the in-memory table as JSON; returns the entry count."""
    doc = {"lanes": effective_lanes(),
           "entries": {",".join(str(k) for k in key): list(cfg)
                       for key, cfg in sorted(_TABLE.items())}}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return len(_TABLE)


def load_table(path: str) -> int:
    """Merge a JSON tuning table into the in-memory one (one version bump);
    returns the number of entries loaded."""
    global _VERSION
    with open(path) as f:
        doc = json.load(f)
    entries = doc.get("entries", {})
    for skey, val in entries.items():
        key = tuple(int(p) for p in skey.split(","))
        if len(key) != 4 or len(val) != 2:
            raise ValueError(f"malformed tuning entry {skey!r}: {val!r}")
        _TABLE[key] = AttnConfig(int(val[0]), int(val[1]))
    _VERSION += 1
    return len(entries)


# ------------------------------------------------------------------ sweep

def sweep(shapes: Iterable[ShapeKey],
          measure: Optional[Callable[[ShapeKey, AttnConfig], float]] = None,
          lanes: Optional[int] = None) -> Dict[ShapeKey, AttnConfig]:
    """Populate the table for ``shapes``: score every candidate split with
    ``measure((hd, bs, nbt, bh), cfg) -> seconds`` (wall-clock on a real
    TPU) or, when None, with the occupancy model (interpret/CPU mode, where
    grid parallelism is not observable).  Deterministic given its inputs;
    returns the chosen configs (also stored via ``put_config``)."""
    lanes = effective_lanes() if lanes is None else lanes
    chosen: Dict[ShapeKey, AttnConfig] = {}
    for key in shapes:
        hd, bs, nbt, bh = (int(k) for k in key)
        best_cfg, best_t = None, None
        for ns in candidate_splits(nbt):
            cfg = AttnConfig(default_block_k(hd), ns)
            t = (measure((hd, bs, nbt, bh), cfg) if measure is not None
                 else modeled_grid_time(bh, nbt, ns, lanes))
            if best_t is None or t < best_t:
                best_cfg, best_t = cfg, t
        chosen[(hd, bs, nbt, bh)] = best_cfg
        put_config((hd, bs, nbt, bh), best_cfg)
    return chosen
