"""Profiler trace capture and its reduction to device and host intervals.

The traced run records a window with `jax.profiler` and brackets it with a
host annotation named WINDOW. `reduce_xplane` turns the `.xplane.pb` into
a small JSON-able dict, all times in seconds from the window's start:

    {"window_s": float,
     "chips": int,
     "device_ops": [[name, start, dur, chip], ...],  # chip 0..n-1
     "host_spans": [[name, start, dur], ...]}   # the bench's annotations

Only that dict reaches the metric readers, so a reader is checked on a
small recorded one (bench/tests/data/).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench.window"
TRACE_S = 8.0          # seconds a --trace 1 run profiles, mid-window
SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"


def _planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xplane(path: str) -> dict:
    host, device = [], defaultdict(list)
    for plane in _planes(path):
        if plane.name.startswith(DEVICE_PLANE):
            chip = plane.name[len(DEVICE_PLANE):]
            for line in plane.lines:
                if line.name == OP_LINE:
                    device[chip] += [(ev.name, ev.start_ns, ev.duration_ns)
                                     for ev in line.events]
        else:
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX)]
    win = [h for h in host if h[0] == WINDOW]
    if not win or not device:
        raise ValueError(f"trace {path} has no {WINDOW} span or no "
                         f"'{OP_LINE}' line on a {DEVICE_PLANE}* plane")
    t0, dur = win[0][1], win[0][2]

    def rel(evs, *extra):
        return [[n, (s - t0) / 1e9, d / 1e9, *extra] for n, s, d in evs
                if s + d > t0 and s < t0 + dur]

    ops = []
    for i, chip in enumerate(sorted(device)):
        ops += rel(device[chip], i)
    return {"window_s": dur / 1e9, "chips": len(device),
            "device_ops": sorted(ops, key=lambda e: e[1]),
            "host_spans": sorted(rel([h for h in host if h[0] != WINDOW]),
                                 key=lambda e: e[1])}


def describe(path: str, limit: int = 40) -> dict:
    """Plane and line names with a few event names each: what a reader
    needs to see once before it is written against a trace."""
    out = {}
    for plane in _planes(path):
        lines = {}
        for line in plane.lines:
            names = []
            for ev in line.events:
                if ev.name not in names:
                    names.append(ev.name)
                if len(names) >= limit:
                    break
            lines[line.name] = names
        out[plane.name] = lines
    return out
