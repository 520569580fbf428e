"""Spans and counters of the program's own stages.

The program's one facility for timing its stages (the frame server's
transfer, readback and resolution; the compiler's mapping and lowering):

- ``span(name, **ids)`` — a context manager around host work.  While a
  JAX profiler session runs it opens a
  ``jax.profiler.TraceAnnotation(name, **ids)``, so the span lands in the
  trace on the same clock as the device's operations, and keeps
  ``(name, t0, t1, ids)`` in a bounded in-memory ring (``records()``).
  A span without ids times a phase that runs a few times in all (the
  compiler's, the warm-up); it also adds its duration to an always-on
  per-name total (``totals()``: calls, seconds), so set-up is counted
  before any profiler starts.  A span with ids times one item of a
  stream (a batch) and keeps nothing with no profiler running.
- ``interval(name, t0, t1, **ids)`` — a wait that has already elapsed
  (a frame's time in the request queue).  It is not host work, so it is
  kept in the ring while profiling and written to no trace.
- ``count(name, n)`` — adds to a counter while profiling (``counters()``).

Names are bare (``serve.d2h``); batch and request ids go in ``ids``, which
become the trace event's stats.  Times are ``time.perf_counter`` seconds.
With no profiler running a span costs two clock reads and one profiler
check (and, for a phase, one locked dict update); the ring and the
counters stay empty.

JAX is looked up only once something has imported it: a profiler session
cannot run before that, and numpy-only flows stay free of it.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

RING = 1 << 18                  # records kept while profiling, newest last


class Record(NamedTuple):
    name: str
    t0: float
    t1: float
    ids: Dict[str, int]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_ring: collections.deque = collections.deque(maxlen=RING)
_counters: Dict[str, int] = {}
_totals: Dict[str, List[float]] = {}      # name -> [calls, seconds]
_lock = threading.Lock()
_annotation = None


def _profiler():
    """``TraceAnnotation`` if a profiler session is running, else None."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation if _annotation.is_enabled() else None


def enabled() -> bool:
    """True while a profiler session runs (records and counters are kept)."""
    return _profiler() is not None


class span:
    """Time the enclosed host work under ``name`` (see the module doc).

    ``seconds`` holds the duration once the block has exited, so a caller
    can report the same clock reading the total got."""

    __slots__ = ("name", "ids", "seconds", "_t0", "_ann")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.seconds = 0.0

    def __enter__(self) -> "span":
        annotation = _profiler()
        self._ann = None
        if annotation is not None:
            self._ann = annotation(self.name, **self.ids)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.seconds = t1 - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            _ring.append(Record(self.name, self._t0, t1, self.ids))
        if self.ids:
            return
        with _lock:
            tot = _totals.setdefault(self.name, [0, 0.0])
            tot[0] += 1
            tot[1] += self.seconds


def interval(name: str, t0: float, t1: float, **ids) -> None:
    """Keep an already elapsed wait ``[t0, t1]`` while profiling."""
    if enabled():
        _ring.append(Record(name, t0, t1, ids))


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` while profiling."""
    if enabled():
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


def records(name: Optional[str] = None) -> List[Record]:
    """The ring's records (of ``name`` only, if given), oldest first."""
    recs = list(_ring)
    return recs if name is None else [r for r in recs if r.name == name]


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def totals() -> Dict[str, Tuple[int, float]]:
    """Every phase (span without ids) seen: (calls, seconds), profiler on
    or off."""
    with _lock:
        return {k: (int(c), s) for k, (c, s) in _totals.items()}


def clear() -> None:
    """Forget every record, counter and total."""
    with _lock:
        _ring.clear()
        _counters.clear()
        _totals.clear()
