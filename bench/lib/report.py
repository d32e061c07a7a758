"""A run's device record; device busy time and the breakdown of a
reduced trace."""

from __future__ import annotations

from collections import defaultdict

from bench.lib import stats
from bench.lib.trace import SPAN_PREFIX

TOP = 10
# ops that contain other ops of the same line (a scan's loop)
CONTAINERS = ("while", "conditional", "call")


def device(chips) -> dict:
    """Platform, kind and count of the cell's chips as JAX reports them,
    the peak bytes in use on each, and that of the fullest."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in chips]
    return {"platform": chips[0].platform, "kind": chips[0].device_kind,
            "count": len(chips), "memory_peak_bytes": max(peaks),
            "memory_peak_bytes_per_chip": peaks}


def short_name(op: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return op.split(" = ")[0].lstrip("%")


def busy_s(tr: dict) -> float:
    """Seconds with an op running, averaged over the chips."""
    per_chip = defaultdict(list)
    for _, s, d, chip in tr["device_ops"]:
        per_chip[chip].append((s, s + d))
    return sum(stats.busy_seconds(v, 0.0, tr["window_s"])
               for v in per_chip.values()) / max(tr["chips"], 1)


def _label(spans, t: float) -> str:
    """Innermost bench span open at time t (the latest to start)."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or s >= best[1]):
            best = (name, s)
    return best[0][len(SPAN_PREFIX):] if best else "outside any span"


def breakdown(tr: dict) -> dict:
    by_op = defaultdict(float)
    for name, _, d, _ in tr["device_ops"]:
        short = short_name(name)
        if short.split(".")[0] not in CONTAINERS:
            by_op[short] += d
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    chip0 = [(s, s + d) for _, s, d, chip in tr["device_ops"] if chip == 0]
    spans = [h[:3] for h in tr["host_spans"]]
    gaps = sorted(stats.idle_gaps(chip0, 0.0, tr["window_s"]),
                  key=lambda g: g[0] - g[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[_label(spans, (a + b) / 2), b - a]
                          for a, b in gaps]}
