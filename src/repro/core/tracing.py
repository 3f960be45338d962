"""Spans and counters at the search's layer boundaries.

One process-wide recorder.  The engine, the optimizers' ask, the accelerated
GP, the store boundaries and the connector lifecycle open named spans and
bump named counters here; a profiler run, the chip benchmark's readers and
``python -m repro.core.api run --profile`` read them back.

* ``span(name)`` — a context manager around one piece of work;
* ``count(name, n=1)`` — adds ``n`` to a counter;
* ``trial(seq)`` — the trial id that spans opened afterwards on this thread
  carry;
* ``spans()``, ``counters()``, ``dropped()`` — what was recorded;
  ``reset()`` forgets it.

Recording is on after ``enable()``, and whenever a JAX profiler session is
recording host events (jaxlib's ``TraceMe.is_enabled()``, asked only once
``jax`` is imported): a profiled process records its spans with no further
call.  When recording is off, ``span`` returns one shared null context: no
clock read, no allocation, no annotation.

A span records ``(name, t0, t1, parent, trial)``: ``t0``/``t1`` on
``time.perf_counter``; ``parent`` the index, in ``spans()``, of the span
that enclosed it on the same thread (None at the top).  With ``jax``
imported it also opens ``jax.profiler.TraceAnnotation("repro:<name>")``, so
a profiler trace holds it on the host plane, on the device planes' clock.
Records go to a bounded buffer; a span opened while the buffer is full is
dropped and counted.  A span's self time is its duration less the union of
its children's intervals.

Importing this module imports no jax: ``repro.core`` is imported by every
worker process the execution backends start.
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from typing import NamedTuple, Optional

__all__ = ["Span", "span", "count", "trial", "enable", "recording", "spans",
           "counters", "dropped", "reset", "self_times", "summary",
           "write_jsonl", "CAPACITY"]

#: Most spans the buffer holds between resets.
CAPACITY = 1 << 20

#: What ``span`` returns while recording is off.
_NULL = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    t0: float
    t1: Optional[float]        # None while the span is still open
    parent: Optional[int]      # index of the enclosing span, same thread
    trial: Optional[int]


class _State(threading.local):
    def __init__(self):
        self.stack: list = []      # (index, generation) of open spans
        self.trial: Optional[int] = None


_enabled = False
_profiler_on = None                # TraceMe.is_enabled, once jax is imported
_annotation = None                 # jax.profiler.TraceAnnotation
_records: list = []
_counters: dict = {}
_dropped = 0
_generation = 0                    # bumped by reset: open spans then drop
_lock = threading.Lock()
_local = _State()


def _jax_hooks() -> bool:
    """Bind the profiler's probe and annotation once jax is imported."""
    global _profiler_on, _annotation
    if "jax" not in sys.modules:
        return False
    import jax.profiler
    _annotation = jax.profiler.TraceAnnotation
    _profiler_on = _annotation.is_enabled
    return True


def recording() -> bool:
    """True while spans and counters are recorded."""
    if _enabled:
        return True
    if _profiler_on is None and not _jax_hooks():
        return False
    return _profiler_on()


def enable(on: bool = True) -> None:
    """Record whether or not a profiler session is running."""
    global _enabled
    _enabled = bool(on)


class _Open:
    __slots__ = ("name", "index", "generation", "annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        global _dropped
        if _annotation is not None or _jax_hooks():
            self.annotation = _annotation(f"repro:{self.name}")
            self.annotation.__enter__()
        else:
            self.annotation = None
        stack = _local.stack
        t0 = time.perf_counter()
        with _lock:
            self.generation = _generation
            # an enclosing span opened before a reset is not in the buffer
            parent = stack[-1][0] if stack and stack[-1][1] == _generation \
                else None
            if len(_records) < CAPACITY:
                self.index = len(_records)
                _records.append(Span(self.name, t0, None, parent,
                                     _local.trial))
            else:
                self.index = None
                _dropped += 1
        # a dropped span's children attach to its parent
        stack.append((self.index if self.index is not None else parent,
                      self.generation))
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _local.stack.pop()
        if self.index is not None:
            with _lock:
                if self.generation == _generation:
                    _records[self.index] = _records[self.index]._replace(t1=t1)
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False


def span(name: str):
    """A context manager that records the enclosed work as ``name``."""
    if not recording():
        return _NULL
    return _Open(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if not recording():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def trial(seq: Optional[int]) -> None:
    """Spans this thread opens from now on carry trial id ``seq``."""
    if recording():
        _local.trial = seq


def spans() -> list:
    """Every recorded :class:`Span`, in the order they were opened (the
    order ``parent`` indexes)."""
    with _lock:
        return list(_records)


def counters() -> dict:
    with _lock:
        return dict(_counters)


def dropped() -> int:
    """Spans not recorded because the buffer was full."""
    return _dropped


def reset() -> None:
    """Forget every span and counter; spans open now are not recorded."""
    global _dropped, _generation
    with _lock:
        _records.clear()
        _counters.clear()
        _dropped = 0
        _generation += 1


def self_times(records: list) -> list:
    """Each span's duration less the union of its closed children's
    intervals (None for a span still open)."""
    children: dict = {}
    for s in records:
        if s.parent is not None and s.t1 is not None:
            children.setdefault(s.parent, []).append((s.t0, s.t1))
    out = []
    for i, s in enumerate(records):
        if s.t1 is None:
            out.append(None)
            continue
        covered, end = 0.0, s.t0
        for a, b in sorted(children.get(i, ())):
            a, b = max(a, end), min(b, s.t1)
            if b > a:
                covered += b - a
                end = b
        out.append((s.t1 - s.t0) - covered)
    return out


def summary(records: list) -> list:
    """One row per span name, by total time: ``(name, count, total_s,
    self_s, mean_s, p95_s)``."""
    rows: dict = {}
    for s, own in zip(records, self_times(records)):
        if s.t1 is None:
            continue
        row = rows.setdefault(s.name, [[], 0.0])
        row[0].append(s.t1 - s.t0)
        row[1] += own
    out = []
    for name, (durations, own) in rows.items():
        durations.sort()
        # linear interpolation between order statistics, as numpy's default
        k = 0.95 * (len(durations) - 1)
        lo = int(k)
        hi = min(lo + 1, len(durations) - 1)
        p95 = durations[lo] + (k - lo) * (durations[hi] - durations[lo])
        out.append((name, len(durations), sum(durations), own,
                    sum(durations) / len(durations), p95))
    return sorted(out, key=lambda r: -r[2])


def write_jsonl(path: str) -> int:
    """Write every closed span as one JSON line (``i``, ``name``, ``t0``,
    ``t1``, ``parent``, ``trial``, ``self_s``; seconds on
    ``time.perf_counter``), then one line ``{"counters": ..., "dropped":
    n}``.  Returns the number of spans written."""
    records = spans()
    n = 0
    with open(path, "w") as f:
        for i, (s, own) in enumerate(zip(records, self_times(records))):
            if s.t1 is None:
                continue
            f.write(json.dumps({"i": i, "name": s.name, "t0": s.t0,
                                "t1": s.t1, "parent": s.parent,
                                "trial": s.trial, "self_s": own}) + "\n")
            n += 1
        f.write(json.dumps({"counters": counters(),
                            "dropped": dropped()}) + "\n")
    return n
