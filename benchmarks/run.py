"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,fig9]

Prints `name,us_per_call,derived` CSV (harness contract)."""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback


MODULES = ["table1_mse", "fig9_unbiasedness", "table2_bandwidth",
           "kernel_overhead", "fig2_forward_ablation",
           "fig1_backward_ablation", "fig4_full_quant", "nanochat_style",
           "serve_throughput"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-closer sizes/steps (slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="<30s-per-module CPU path (CI): forces quick sizes "
                         "and trims training steps via benchmarks.common.SMOKE")
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    args = ap.parse_args()
    mods = MODULES
    if args.only:
        keys = args.only.split(",")
        mods = [m for m in MODULES if any(m.startswith(k) for k in keys)]
    if mods == ["serve_throughput"]:
        # two simulated host-platform devices for the serve/decode_sharded
        # row — ONLY for an explicitly serve-only run (`--only serve`): the
        # device count is process-wide and must precede the first jax
        # import, so forcing it in a mixed run would silently change the
        # measurement environment of every other bench. Mixed/default runs
        # keep the pristine single-device environment and the sharded row
        # degrades to data_shards=1 (recorded in its derived column /
        # BENCH_serve.json, so the artifact stays self-describing).
        # setdefault keeps explicit operator XLA_FLAGS intact.
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    if args.smoke:
        from benchmarks import common
        common.SMOKE = True
        args.full = False
    # first jax import: after the device-count flag above
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    print("name,us_per_call,derived")
    ok = True
    for name in mods:
        t0 = time.time()
        try:
            # free compiled executables between modules: XLA-CPU's JIT dylib
            # table is finite and the training benches compile many programs
            import jax
            jax.clear_caches()
            mod = importlib.import_module(f"benchmarks.{name}")
            for row, us, derived in mod.run(quick=not args.full):
                print(f"{row},{us:.1f},{derived}")
        except Exception:
            ok = False
            traceback.print_exc()
            print(f"{name},nan,FAILED")
        print(f"# {name} took {time.time() - t0:.1f}s", file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
