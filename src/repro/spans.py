"""Host spans of the serving engine, on the profiler's clock.

``span(name, **attrs)`` times a block of host work.  While the recorder is
on, a span opens a ``jax.profiler.TraceAnnotation``, so a profiler trace
holds it, with its attributes as the event's stats, on the host plane and
on the device operations' clock; a ``gc.callbacks`` hook does the same for
each full (generation 2) collection, as ``host.gc``.  While it is off,
``span()`` returns one shared no-op: one flag check, no allocation, no JAX
call.

The recorder is on while a profiler trace collects host events (the
engine calls ``follow_profiler()`` at the start of each tick) and between
``enable()`` and ``disable()``.  Only ``enable()`` also keeps the spans in
memory, as ``(name, t0, t1, parent, attrs)`` on ``time.perf_counter``, for
``drain()`` to hand over: records stand in the order their spans opened,
and ``parent`` is the enclosing span's index among them (-1 at the top).
Spans are recorded from one thread.
"""
from __future__ import annotations

import functools
import gc
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

Record = Tuple[str, float, float, int, Dict]

_on = False          # spans open annotations
_keep = False        # enable(): spans are also kept for drain()
_records: List = []  # Record, or None while the span is open
_open: List[int] = []   # indices of the open kept spans, innermost last
_gc: List = []       # (t0, annotation) of the collection under way


class _Off:
    """The span of a recorder that is off: falsy, does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs):
        pass


OFF = _Off()


class _Span:
    __slots__ = ("name", "attrs", "ann", "i", "parent", "t0", "of")

    def __init__(self, name: str, attrs: Dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.ann = TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.of = _records if _keep else None
        if self.of is not None:
            self.parent = _open[-1] if _open else -1
            self.i = len(_records)
            _records.append(None)
            _open.append(self.i)
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.of is not None:
            t1 = time.perf_counter()
            _open.pop()
            # a drain() while the span was open took its slot with it
            if self.of is _records:
                _records[self.i] = (self.name, self.t0, t1, self.parent,
                                    self.attrs)
        self.ann.__exit__(*exc)
        return False

    def set(self, **attrs):
        """Attributes known only once the span's work is under way."""
        self.attrs.update(attrs)
        self.ann.set_metadata(**attrs)


def span(name: str, **attrs):
    """A context manager timing the block inside it as ``name``."""
    if not _on:
        return OFF
    return _Span(name, attrs)


def wrap(name: str, fn: Callable,
         attrs: Optional[Callable[..., Dict]] = None) -> Callable:
    """``fn`` with each call inside a span ``name``; ``attrs(*args)`` gives
    the span's attributes from the call's arguments."""
    @functools.wraps(fn)
    def call(*args):
        if not _on:
            return fn(*args)
        with _Span(name, attrs(*args) if attrs else {}):
            return fn(*args)
    return call


def _collection(phase: str, info: Dict):
    # young generations take microseconds; only a full collection is a
    # pause worth a span
    if info["generation"] < 2:
        return
    if phase == "start":
        ann = TraceAnnotation("host.gc")
        ann.__enter__()
        _gc.append((time.perf_counter(), ann))
    elif _gc:
        t0, ann = _gc.pop()
        ann.set_metadata(collected=info["collected"])
        if _keep:
            _records.append(("host.gc", t0, time.perf_counter(),
                             _open[-1] if _open else -1,
                             {"generation": 2,
                              "collected": info["collected"]}))
        ann.__exit__(None, None, None)


def _set(on: bool):
    global _on
    if on == _on:
        return
    _on = on
    if on:
        gc.callbacks.append(_collection)
    else:
        gc.callbacks.remove(_collection)


def enable():
    """Record, and keep the records, until ``disable()``."""
    global _keep
    _keep = True
    _set(True)


def disable():
    global _keep
    _keep = False
    _set(False)


def follow_profiler():
    """Record while a profiler trace collects host events or ``enable()``
    holds the recorder on; stop otherwise."""
    _set(_keep or TraceAnnotation.is_enabled())


def drain() -> List[Optional[Record]]:
    """Hand over the kept records (None for a span still open) and start
    a new list.  Call it between spans: ``parent`` indices count from the
    list's start."""
    global _records
    out, _records = _records, []
    return out
