"""What a profiler trace holds beyond `trace.reduce_xplane`'s reduction: the
program's own host spans, and the JAX scope of every device op.

`extend(tr, path)` returns the reduced trace `tr` of the `.xplane.pb` at
`path` with three more keys, times in seconds from the window's start as
in `tr`:

    "program_spans": [[name, start, dur, thread], ...]  # host events serve.*
    "op_scopes": {device op name: tf_op path}
    "op_scope_clashes": int   # op names two ops share with different
                              # paths; left out of op_scopes

`thread` numbers the host lines (one per thread) in trace order. The
`tf_op` path is the op's JAX name stack (`jit(f)/jvp()/.../quant.fwd/mul`),
a stat of the op's XEventMetadata on the `/device:*` planes. The profiler's
`ProfileData` does not expose metadata stats and no XPlane protobuf module
is installed, so a small reader of the protobuf wire format reads them.

A metric reader is handed only the reduced trace; `with_meta(tr)` finds the
file it came from (the newest trace under bench_out/, checked against the
window's length) and extends it. On a program without `serve.*` spans or
named scopes the new keys are empty and the readers return None.

    python3 bench/lib/xplane_meta.py <trace.xplane.pb>

prints the device's idle seconds under each innermost program span (a
serving trace) and the device time by scope (a training trace).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from bisect import bisect_right
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.lib import report, stats  # noqa: E402
from bench.lib import trace as T  # noqa: E402

OUT = Path(__file__).resolve().parents[2] / "bench_out"   # bench/run.py's
PROGRAM_PREFIX = "serve."
TICK, SYNC = "serve.tick", "serve.sync"
TF_OP = "tf_op"
# the program's named scopes (src/repro/core/scopes.py), innermost wins
SCOPES = ("gemm", "quant.fwd", "quant.bwd", "rht", "optimizer")
QUANTIZER_SCOPES = ("quant.fwd", "quant.bwd", "rht")


# --------------------------------------------------------------------------
# protobuf wire format: just enough to walk XSpace -> XPlane -> metadata
# --------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message in b[i:end]: varints as ints,
    length-delimited fields as (start, end) ranges, fixed widths skipped."""
    while i < end:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _str(b: bytes, r) -> str:
    return b[r[0]:r[1]].decode("utf-8", "replace")


def _map_entries(b: bytes, r):
    """(key, value range) of one map<int64, message> entry."""
    key = val = None
    for num, v in _fields(b, *r):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def device_op_paths(path: str) -> list[tuple[str, str]]:
    """(op name, tf_op path) of every XEventMetadata with a tf_op stat on
    the /device:* planes (XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2,
    stats = 5; XStat.metadata_id = 1, str_value = 5, ref_value = 7;
    XStatMetadata.name = 2)."""
    b = Path(path).read_bytes()
    out = []
    for num, plane in _fields(b, 0, len(b)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(b, *plane):
            if pnum == 2:
                name = _str(b, v)
            elif pnum == 4:
                events.append(_map_entries(b, v)[1])
            elif pnum == 5:
                key, val = _map_entries(b, v)
                stat_names[key] = next((_str(b, sv) for snum, sv
                                        in _fields(b, *val) if snum == 2), "")
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, n in stat_names.items() if n == TF_OP}
        for ev in events:
            op, scope = None, None
            for enum, v in _fields(b, *ev):
                if enum == 2:
                    op = _str(b, v)
                elif enum == 5:
                    stat = dict(_fields(b, *v))
                    if stat.get(1) not in tf_op:
                        continue
                    if 5 in stat:
                        scope = _str(b, stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
            if op is not None and scope:
                # "<name stack>:<op type>", the op type often empty
                out.append((op, scope.rsplit(":", 1)[0]))
    return out


def op_scopes(path: str) -> tuple[dict, int]:
    """{op name: tf_op path}, and the number of names left out because two
    ops (of two programs) share the name with different paths."""
    seen: dict = {}
    clashes = set()
    for op, scope in device_op_paths(path):
        if seen.setdefault(op, scope) != scope:
            clashes.add(op)
    for op in clashes:
        del seen[op]
    return seen, len(clashes)


# --------------------------------------------------------------------------
# the program's host spans
# --------------------------------------------------------------------------

def program_spans(path: str) -> tuple[list, float | None]:
    """The host events named serve.* that overlap the window, relative to
    its start, and the window's length (None without a window span)."""
    events, t0, dur, thread = [], None, None, 0
    for plane in T._planes(path):
        if plane.name.startswith(T.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == T.WINDOW:
                    t0, dur = ev.start_ns, ev.duration_ns
                elif ev.name.startswith(PROGRAM_PREFIX):
                    events.append((ev.name, ev.start_ns, ev.duration_ns,
                                   thread))
            thread += 1
    if t0 is None:
        return [], None
    spans = [[n, (s - t0) / 1e9, d / 1e9, th] for n, s, d, th in events
             if s + d > t0 and s < t0 + dur]
    return sorted(spans, key=lambda e: e[1]), dur / 1e9


def extend(tr: dict, path: str) -> dict | None:
    """tr with the three keys read from `path`; None when the file's
    window is not tr's (tr was reduced from another file)."""
    spans, window_s = program_spans(path)
    if window_s is None or abs(window_s - tr["window_s"]) > 1e-9:
        return None
    scopes, clashes = op_scopes(path)
    return dict(tr, program_spans=spans, op_scopes=scopes,
                op_scope_clashes=clashes)


def with_meta(tr: dict | None) -> dict | None:
    """`tr` extended from the trace file it was reduced from, the newest
    .xplane.pb under bench_out/ (each run profiles once, and reduces its
    trace before any reader runs). None when there is no trace or that
    file is not tr's."""
    if tr is None or "op_scopes" in tr:
        return tr
    found = sorted(glob.glob(str(OUT / "*" / "trace" / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return extend(tr, found[-1]) if found else None


# --------------------------------------------------------------------------
# what the readers and PERF.md read
# --------------------------------------------------------------------------

def tick_host_s(tr: dict) -> list[float]:
    """Per serve.tick span wholly inside the window: its duration less the
    serve.sync time inside it (on its thread)."""
    spans = tr["program_spans"]
    syncs = defaultdict(list)
    for name, s, d, th in spans:
        if name == SYNC:
            syncs[th].append((s, d))
    out = []
    for name, s, d, th in spans:
        if name != TICK or s < 0 or s + d > tr["window_s"]:
            continue
        inside = sum(sd for ss, sd in syncs[th] if ss >= s and ss + sd <= s + d)
        out.append(d - inside)
    return out


def scopes_on(path: str | None) -> list[str]:
    """The program's scopes on a tf_op path, outermost first (a fused op's
    path may join op names with ';')."""
    return [c for c in re.split("[/;]", path or "") if c in SCOPES]


def scope_of(path: str | None) -> str | None:
    """The innermost of the program's scopes on a tf_op path."""
    comps = scopes_on(path)
    return comps[-1] if comps else None


def device_s_by_scope(tr: dict) -> dict:
    """Device seconds in the window by innermost scope, averaged over the
    chips; a gemm under transpose( is the backward's ("gemm.bwd"), ops
    under no scope "other", containers (a scan's loop) left out."""
    out = defaultdict(float)
    w, paths = tr["window_s"], tr["op_scopes"]
    for name, s, d, _ in tr["device_ops"]:
        if report.short_name(name).split(".")[0] in report.CONTAINERS:
            continue
        path = paths.get(name)
        sc = scope_of(path) or "other"
        if sc == "gemm":
            sc = "gemm.bwd" if "transpose(" in path else "gemm.fwd"
        out[sc] += max(0.0, min(s + d, w) - max(s, 0.0))
    return {k: v / max(tr["chips"], 1) for k, v in out.items()}


def idle_by_span(tr: dict) -> dict:
    """Chip 0's idle seconds in the window by the innermost program span
    open on the engine thread (the thread of the serve.tick spans), split
    exactly at span edges; "outside any span" for the rest."""
    ticks = [th for n, _, _, th in tr["program_spans"] if n == TICK]
    if not ticks:
        return {}
    engine = max(set(ticks), key=ticks.count)
    spans = sorted((s, s + d, n) for n, s, d, th in tr["program_spans"]
                   if th == engine)
    starts = [s for s, _, _ in spans]
    busy = [(s, s + d) for _, s, d, chip in tr["device_ops"] if chip == 0]
    out = defaultdict(float)
    for a, b in stats.idle_gaps(busy, 0.0, tr["window_s"]):
        near = [sp for sp in spans[:bisect_right(starts, b)] if sp[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in near for t in (s, e)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            open_ = [sp for sp in near if sp[0] <= mid < sp[1]]
            name = max(open_)[2] if open_ else "outside any span"
            out[name] += hi - lo
    return dict(out)


def main(path: str) -> None:
    tr = extend(T.reduce_xplane(path), path)
    idle = tr["window_s"] - report.busy_s(tr)
    by_span = idle_by_span(tr)
    print(json.dumps({
        "window_s": tr["window_s"], "busy_s": report.busy_s(tr),
        "idle_s": idle, "program_spans": len(tr["program_spans"]),
        "scoped_ops": len(tr["op_scopes"]),
        "op_scope_clashes": tr["op_scope_clashes"],
        "idle_s_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "idle_share_in_spans": (1 - by_span.get("outside any span", 0) / idle
                                if by_span and idle > 0 else None),
        "tick_host_ms": [1e3 * x for x in tick_host_s(tr)],
        "device_s_by_scope": device_s_by_scope(tr)}))


if __name__ == "__main__":
    main(sys.argv[1])
