"""The program's own spans and counters (``repro.core.tracing``), as the
per-layer readers see them.

The program records them whenever a profiler session is on, so in a traced
run they cover the window; a reader keeps the spans that lie inside the
window's ``[t0, t1]``.  The counters are totals since the process started,
and the program counts only while the profiler is on, which the harness
turns on for the window alone.  A program without ``repro.core.tracing``
has nothing to read: every helper here returns None.
"""

from __future__ import annotations


def _tracing():
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing


def in_window(ctx) -> list | None:
    """``(span, self_s)`` of every closed span inside the window."""
    tracing = _tracing()
    if tracing is None:
        return None
    w = ctx["window"]
    records = tracing.spans()
    return [(s, own) for s, own in zip(records, tracing.self_times(records))
            if s.t1 is not None and s.t0 >= w.t0 and s.t1 <= w.t1]


def total(ctx, names, self_time: bool = False) -> tuple:
    """(seconds, number of spans) of the spans named in ``names`` (or
    whose name starts with ``names`` when it is a string ending in
    ``.``); self time with ``self_time``.  (None, 0) with no tracing."""
    spans = in_window(ctx)
    if spans is None:
        return None, 0
    if isinstance(names, str):
        picked = [(s, own) for s, own in spans if s.name.startswith(names)]
    else:
        picked = [(s, own) for s, own in spans if s.name in names]
    seconds = sum(own if self_time else s.t1 - s.t0 for s, own in picked)
    return seconds, len(picked)


def counters(ctx) -> dict | None:
    tracing = _tracing()
    return None if tracing is None else tracing.counters()


def asks(ctx) -> int:
    """The benchmark's count of the window's asks, the base of every
    per-ask metric (as ``gp_device_ms.per_ask`` counts them)."""
    w = ctx["window"]
    return len(ctx["spans"].durations("ask", w.t0, w.t1))


def per_ask_ms(ctx, names, self_time: bool = False):
    seconds, n = total(ctx, names, self_time)
    k = asks(ctx)
    if not n or not k:
        return None
    return 1e3 * seconds / k


def per_trial_ms(ctx, names, self_time: bool = False):
    seconds, n = total(ctx, names, self_time)
    k = len(ctx["window"].trials)
    if not n or not k:
        return None
    return 1e3 * seconds / k
