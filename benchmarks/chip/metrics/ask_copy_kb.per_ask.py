"""Bytes the ask's GP calls copy between host and device (the program's
``device.h2d_bytes``: every host array passed to a jitted fit or score,
and ``device.d2h_bytes``: every readback), in kB per ask of the window."""

import program_spans

COUNTERS = ("device.h2d_bytes", "device.d2h_bytes")


def read(ctx):
    counts = program_spans.counters(ctx)
    asks = program_spans.asks(ctx)
    if not counts or not asks or not any(c in counts for c in COUNTERS):
        return None
    return sum(counts.get(c, 0) for c in COUNTERS) / 1e3 / asks
