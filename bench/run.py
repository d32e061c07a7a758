#!/usr/bin/env python3
"""One run of one benchmark cell on the chip(s) it runs on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. BENCHMARK.json names the cell; its
configuration, traffic mix and per-layer readers are found by name under
bench/ (bench/README.md). Exits non-zero, printing no result, when the
program is not beside the benchmark, when a serving mesh does not take
the cell's chips, when JAX sees no TPU or fewer chips than the cell asks
for (with a mesh: other than the cell's chips), or when the device is not
in bench/peaks.json.

The last line of stdout is one JSON object: correct, attempted, failed,
metrics (the cell's end-to-end metrics, or with --trace 1 its per-layer
metrics), device, with --trace 1 also breakdown, and last the numbers
that decided `correct`, each beside its limit; stderr ends with the same
numbers, one per line.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "bench_out"
CACHE_DIR = ROOT / ".jax_cache"
# the TPU runtime's logs go inside the checkout, not to a fixed /tmp path;
# the runtime does not make the directory itself
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(OUT / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def fail(code: int, msg: str):
    sys.stderr.write(f"bench: {msg}; nothing was run\n")
    sys.exit(code)


def configure_cache(jax) -> None:
    """Persistent compile cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR points), every program kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_gate(jax, chips: int, mesh: bool):
    """The devices JAX sees: a TPU, at least `chips` of them, and with a
    mesh exactly `chips` (the mesh takes every device)."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(3, f"no TPU (JAX sees {devices[0].platform})")
    if len(devices) < chips or (mesh and len(devices) != chips):
        fail(3, f"the cell needs {chips} TPU chips, JAX sees {len(devices)}")
    return devices


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(2, f"the program (src/repro) is not beside {ROOT / 'bench'}")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import cell as C
    from bench.lib import report

    cell = C.load(ROOT, args.workload)
    try:
        mesh = cell.mesh_shape()
    except ValueError as e:
        fail(5, str(e))
    import jax
    configure_cache(jax)
    devices = device_gate(jax, cell.chips, mesh is not None)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        fail(4, f"device kind {kind!r} is not in bench/peaks.json")
    cell.peaks = peaks["devices"][kind]

    if cell.config["kind"] == "serve":
        from bench.lib import serve as runner
    else:
        from bench.lib import train as runner
    res = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     OUT / args.workload, PROCESS_START)
    tr = res.pop("trace")
    res.pop("detail", None)
    check = res.pop("check")
    if args.trace:
        res["device"]["busy_s"] = report.busy_s(tr)
        res["device"]["window_s"] = tr["window_s"]
        res["breakdown"] = report.breakdown(tr)
    res["check"] = check
    for name, v in check.items():
        sys.stderr.write(f"check {name} {v['value']} limit {v['limit']}\n")
    sys.stderr.flush()
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
