"""Reduction of a profiler trace of the window to device and host numbers.

The JAX profiler writes an ``.xplane.pb`` file; ``jax.profiler.ProfileData``
reads it.  Each TPU chip is a plane named ``/device:TPU:<i>``, whose line
``XLA Ops`` holds one event per operation the chip ran and whose line
``XLA Modules`` holds one event per program execution (named after the
jitted function, e.g. ``jit__gp_fit(...)``).  The benchmark's host spans are
``TraceAnnotation`` events named ``bench:<span>`` on the host
plane; ``bench:window`` bounds the window.  All start times share one clock.

* busy: the union of operation intervals inside the window, per chip,
  averaged over the chips;
* idle gaps: the rest of the window, each piece put to the innermost
  host span that covers its middle: ``measure``, ``ask``,
  ``investigation`` (a new investigation's store copy and set-up),
  else ``engine_store`` (the engine, the store's record and the tell);
* per program and per operation: summed device durations.
"""

from __future__ import annotations

import glob
import json
import os
from pathlib import Path

HERE = Path(__file__).resolve().parent

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# innermost first: a gap inside an ask span that lies inside an
# investigation is the ask's
HOST_ORDER = ("measure", "ask", "investigation")


def peaks_for(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json knows {sorted(table)}")
    return table[device_kind]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            and p.name[len("/device:TPU:"):].isdigit()]


def load(tracedir: str):
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tracedir}")
    return ProfileData.from_file(files[-1])


def reduce_profile(pd) -> dict:
    """Everything the metric readers and the breakdown need, as plain data
    (times in seconds from the trace's origin)."""
    host_spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench:"):
                    host_spans.append((ev.name[len("bench:"):],
                                       ev.start_ns * 1e-9,
                                       (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in host_spans if n == "window"]
    if not windows:
        raise ValueError("the trace holds no bench:window span")
    lo, hi = windows[0]

    chips = []
    for plane in _device_planes(pd):
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                modules = [(ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events]
        chips.append({"name": plane.name, "ops": ops, "modules": modules})
    return {"window": (lo, hi), "host": host_spans, "chips": chips}


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``; a custom
    call keeps its target (``checkpoint.8 tpu_custom_call``)."""
    name = op.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="' in op:
        name += " " + op.split('custom_call_target="', 1)[1].split('"', 1)[0]
    return name


def _owners(ops: list, modules: list) -> list:
    """The program (module) each operation ran in, by a sweep over both
    lists in time order; ``?`` where none covers it."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for _, s, _ in ops:
        while j < len(mods) and mods[j][2] < s:
            j += 1
        inside = j < len(mods) and mods[j][1] <= s
        out.append(mods[j][0].split("(")[0] if inside else "?")
    return out


def summarize(data: dict) -> dict:
    lo, hi = data["window"]
    window_s = hi - lo
    busy_each, op_time, module_time = [], {}, {}
    gaps_all = []
    for chip in data["chips"]:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in chip["ops"]
               if e > lo and s < hi]
        ops.sort(key=lambda o: o[1])
        busy = _union([(s, e) for _, s, e in ops])
        busy_each.append(sum(e - s for s, e in busy))
        for (n, s, e), owner in zip(ops, _owners(ops, chip["modules"])):
            key = f"{owner}/{short_name(n)}"
            op_time[key] = op_time.get(key, 0.0) + (e - s)
        for n, s, e in chip["modules"]:
            if e > lo and s < hi:
                base = n.split("(")[0]
                module_time[base] = module_time.get(base, 0.0) + (min(e, hi) - max(s, lo))
        t = lo
        for s, e in busy:
            if s > t:
                gaps_all.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps_all.append((t, hi))
    n_chips = max(len(data["chips"]), 1)
    busy_s = sum(busy_each) / n_chips

    idle: dict = {}
    spans = [(n, s, e) for n, s, e in data["host"] if n != "window"]
    for s, e in gaps_all:
        mid = 0.5 * (s + e)
        covering = {n for n, a, b in spans if a <= mid <= b}
        label = next((n for n in HOST_ORDER if n in covering), "engine_store")
        idle[label] = idle.get(label, 0.0) + (e - s) / n_chips
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "module_time": module_time,
        "op_time": op_time,
        "idle": idle,
        "breakdown": {"device_ops": [[n, v / n_chips] for n, v in top_ops],
                      "idle_gaps": [[n, v] for n, v in top_idle]},
    }


def reduce(tracedir: str) -> dict:
    return summarize(reduce_profile(load(tracedir)))
