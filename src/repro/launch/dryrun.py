import os
# 512 host-platform placeholder devices for the production mesh; backend
# optimization level 0 halves compile time with IDENTICAL cost-model output
# (verified: flops/bytes/collectives match default opt bit-for-bit — the
# SPMD partitioner runs either way and we never execute the code). Pinned
# to the CPU, so neither this process nor its `--all` children (which
# inherit the environment) ever take an attached TPU.
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           "--xla_backend_optimization_level=0")
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh, print memory_analysis / cost_analysis, and extract the
roofline terms (compute / memory / collective) from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

The environment lines above MUST precede any jax import: the 16x16(x2-pod)
mesh needs 512 host-platform placeholders, and jax locks the platform and
the device count on first init. Smoke tests and
benches never import this module, so they still see 1 device.
"""

import argparse
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.dist import sharding as SH
from repro.launch import hlo_cost
from repro.launch import specs as SPECS
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.serve.decode import (make_paged_serve_step, make_prefill_step,
                                make_sharded_serve_step)
from repro.train.train_step import make_train_step

# TPU v5e roofline constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
LINK_BW = 50e9            # bytes/s per ICI link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "s4": 1, "u4": 1}

def model_flops(cfg, shape_name: str) -> float:
    """6*N_active*D (train) or 2*N_active*tokens (decode) — 'useful' FLOPs."""
    cell = SHAPES[shape_name]
    p = SPECS.param_specs(cfg)
    total = sum(x.size for x in jax.tree.leaves(p))
    active = total
    if cfg.moe:
        # routed experts beyond top_k are inactive per token
        def expert_count(path, leaf):
            return leaf.size if ("ff/w" in path and leaf.ndim >= 3) else 0
        flat = jax.tree_util.tree_flatten_with_path(p)[0]
        e_params = sum(l.size for pth, l in flat
                       if "ff" in "/".join(str(k) for k in pth)
                       and l.ndim >= 4)
        active = total - e_params * (1 - cfg.moe.top_k / cfg.moe.n_routed)
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    mult = 6 if cell.kind == "train" else 2
    return mult * active * tokens, total, active


def run_cell(arch: str, shape: str, *, multi_pod: bool, scheme: str,
             fsdp: bool | None = None, remat: bool = True,
             hints: bool | None = None, verbose: bool = True,
             serve_sharded: bool = False) -> dict:
    cfg = registry.get(arch)
    cell = SHAPES[shape]
    if cell.name == "long_500k" and not cfg.subquadratic:
        return {"arch": arch, "shape": shape, "skipped":
                "full-attention arch; 500k decode requires sub-quadratic "
                "attention (DESIGN.md Section 4)"}
    if serve_sharded and cell.kind != "decode":
        return {"arch": arch, "shape": shape, "skipped":
                "--serve-sharded applies to decode cells only"}

    # big models need FSDP for optimizer state; small ones stay TP-only
    n_params = sum(x.size for x in jax.tree.leaves(SPECS.param_specs(cfg)))
    if fsdp is None:
        fsdp = n_params > 3e9
    lm.REMAT = remat

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    # Perf iteration 1: Megatron-layout sharding hints in qlinear (see
    # core/linear.py MESH_AXES). Baseline sweep runs without; opt-in via
    # --hints / REPRO_SHARDING_HINTS=1.
    if hints is None:
        hints = os.environ.get("REPRO_SHARDING_HINTS", "0") == "1"
    from repro.core import linear as QL
    if hints:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_size = 1
        for a in dp_axes:
            dp_size *= sizes[a]
        QL.MESH_AXES = {"dp": dp_axes if len(dp_axes) > 1 else dp_axes[0],
                        "tp": "model", "dp_size": dp_size,
                        "tp_size": sizes["model"]}
    else:
        QL.MESH_AXES = None
    t0 = time.time()

    with mesh:
        params_s = SPECS.param_specs(cfg)
        if cell.kind == "train":
            init_state, train_step = make_train_step(
                cfg, scheme, total_steps=10_000, microbatches=1)
            state_s = jax.eval_shape(init_state, params_s)
            state_sh = SH.state_shardings(state_s, mesh, fsdp=fsdp)
            batch_s = SPECS.train_batch_specs(cfg, shape)
            batch_sh = SH.input_shardings(batch_s, mesh)
            jitted = jax.jit(train_step,
                             in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None))
            lowered = jitted.lower(state_s, batch_s)
        elif cell.kind == "prefill":
            fn = make_prefill_step(cfg, scheme)
            batch_s, cache_s = SPECS.prefill_specs(cfg, shape)
            p_sh = SH.state_shardings(params_s, mesh, fsdp=fsdp)
            c_sh = SH.cache_shardings(cache_s, mesh)
            b_sh = SH.input_shardings(batch_s, mesh)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                             out_shardings=(None, c_sh))
            lowered = jitted.lower(params_s, cache_s, batch_s)
        elif cell.kind == "decode" and serve_sharded:
            # the SHARDED engine step (serve/decode.make_sharded_serve_step):
            # slot-affine pool + per-slot LOCAL block tables under a manual
            # shard_map over "data", prequantized (packed NVFP4) weights +
            # head under GSPMD on "model". The before/after pair with the
            # baseline decode cell below is the PR's acceptance measurement:
            # the baseline all-gathers the pool every step (XLA cannot prove
            # a replicated table's rows are device-local); slot affinity
            # makes the same gather provably local, so the only collectives
            # left are activation-sized "model" reductions.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.serve.prequant import prequantize_specs
            data = dict(mesh.shape).get("data", 1)
            if cell.global_batch % data:
                return {"arch": arch, "shape": shape, "skipped":
                        f"decode batch {cell.global_batch} not divisible by "
                        f"the mesh data axis ({data}): slot sharding needs "
                        "equal shard extents"}
            fn = make_sharded_serve_step(cfg, scheme, mesh)
            in_s, cache_s = SPECS.paged_decode_specs(cfg, shape)
            params_q = prequantize_specs(params_s, cfg, scheme)
            p_sh = SH.serve_param_shardings(params_q, mesh)
            c_sh = SH.serve_cache_shardings(cache_s, mesh)
            d_sh = NamedSharding(mesh, P("data"))
            jitted = jax.jit(fn, in_shardings=(
                p_sh, c_sh, d_sh, d_sh, d_sh, d_sh))
            lowered = jitted.lower(params_q, cache_s, in_s["table"],
                                   in_s["tokens"], in_s["pos"], in_s["active"])
        else:  # decode — the engine's paged step (pos vector + block table),
            # so the cost model prices the pool gather/scatter traffic the
            # serving hot path actually moves (not the legacy dense cache).
            # NOTE the collective term it surfaces is real and damning: the
            # generic cache sharding puts the pool's BLOCK axis on "data",
            # and with a replicated block table XLA cannot prove any row's
            # blocks are device-local, so the gather all-gathers the pool
            # every step. That priced pain is what the --serve-sharded cell
            # above makes local (slot-affine pool sharding, per-slot host
            # tables) — and what the paged_attention kernel replaces
            # wholesale on-device.
            fn = make_paged_serve_step(cfg, scheme)
            in_s, cache_s = SPECS.paged_decode_specs(cfg, shape)
            p_sh = SH.state_shardings(params_s, mesh, fsdp=fsdp)
            c_sh = SH.cache_shardings(cache_s, mesh)
            i_sh = SH.input_shardings(in_s, mesh)
            jitted = jax.jit(fn, in_shardings=(
                p_sh, c_sh, i_sh["table"], i_sh["tokens"], i_sh["pos"],
                i_sh["active"]), out_shardings=(None, c_sh))
            lowered = jitted.lower(params_s, cache_s, in_s["table"],
                                   in_s["tokens"], in_s["pos"], in_s["active"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # some jax versions: one dict/program
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    # trip-count-aware cost model (XLA's cost_analysis counts scan bodies
    # once; hlo_cost multiplies by while trip counts) — see hlo_cost.py
    hc = hlo_cost.analyze(hlo)
    coll = dict(hc.coll_by_type)
    coll["total"] = hc.wire_bytes

    flops_dev = hc.flops
    bytes_dev = hc.hbm_bytes
    mf, n_total, n_active = model_flops(cfg, shape)

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll.get("total", 0.0) / LINK_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)

    result = {
        "arch": arch, "shape": shape, "scheme": scheme,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "fsdp": fsdp, "remat": remat,
        "params_total": n_total, "params_active": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
        "xla_flops_per_device_1trip": float(cost.get("flops", 0.0)),
        "xla_bytes_per_device_1trip": float(cost.get("bytes accessed", 0.0)),
        "collectives": {k: v for k, v in coll.items()},
        "memory": {
            "args_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "model_flops": mf,
        "useful_flops_ratio": mf / max(flops_dev * chips, 1.0),
        "roofline": terms,
        "bottleneck": bottleneck,
        # 1.0 == perfectly compute-bound: the dominant term IS the matmuls
        "roofline_fraction": t_compute / max(t_compute, t_memory, t_coll, 1e-30),
    }
    if cell.kind == "decode":
        # pool-collective accounting: the acceptance bar for slot-affine
        # sharding is that NO decode step moves pool-scale collectives.
        # Yardstick: one "data"-shard's pool slice — the baseline paged
        # step's replicated-table gather moves a multiple of it over the
        # wire every step (llama_200m decode_32k: 37.8 GB/dev ~ 3x the
        # 13 GB slice), while the slot-affine sharded step's remaining
        # collectives are activation-sized (~4 MB/dev, "model" reductions)
        pool_bytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree.leaves(cache_s))
        pool_slice = pool_bytes / max(dict(mesh.shape).get("data", 1), 1)
        result["serve_sharded"] = serve_sharded
        result["pool_bytes_global"] = pool_bytes
        result["pool_bytes_per_data_shard"] = pool_slice
        result["no_pool_allgather"] = bool(
            coll.get("total", 0.0) < 0.1 * pool_slice)
    if verbose:
        print(f"[dryrun] {arch} x {shape} on {result['mesh']} ({scheme}) — "
              f"compile {t_compile:.1f}s")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB per device")
        print(f"  cost_analysis: {flops_dev:.3e} flops/dev, "
              f"{bytes_dev:.3e} bytes/dev")
        print(f"  collectives (wire B/dev): " + ", ".join(
            f"{k}={v:.2e}" for k, v in coll.items() if not k.endswith('_count')))
        print(f"  roofline: compute={t_compute*1e3:.2f}ms "
              f"memory={t_memory*1e3:.2f}ms coll={t_coll*1e3:.2f}ms "
              f"-> bottleneck={bottleneck}")
    return result


ALL_CELLS = [(a, s) for a in registry.names() if a != "llama_200m"
             for s in SHAPES]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--scheme", default="quartet2")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--hints", action="store_true",
                    help="qlinear Megatron-layout sharding hints (Perf iter 1)")
    ap.add_argument("--serve-sharded", action="store_true",
                    help="decode cells lower the slot-affine SHARDED serving "
                         "step (shard_map over 'data', prequantized weights "
                         "over 'model') instead of the baseline paged step")
    ap.add_argument("--fsdp", default=None, choices=["on", "off"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--jobs", type=int, default=2)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        # subprocess per cell: isolates compile memory, allows parallelism
        jobs = []
        for arch, shape in ALL_CELLS:
            for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}_{args.scheme}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--scheme", args.scheme, "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                if args.no_remat:
                    cmd.append("--no-remat")
                jobs.append((tag, cmd))
        running: list = []
        while jobs or running:
            while jobs and len(running) < args.jobs:
                tag, cmd = jobs.pop(0)
                print(f"[driver] start {tag} ({len(jobs)} queued)")
                running.append((tag, subprocess.Popen(
                    cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)))
            done = [(t, p) for t, p in running if p.poll() is not None]
            running = [(t, p) for t, p in running if p.poll() is None]
            for tag, p in done:
                out = p.stdout.read().decode()
                status = "ok" if p.returncode == 0 else f"FAIL rc={p.returncode}"
                print(f"[driver] {tag}: {status}")
                if p.returncode != 0:
                    print(out[-2000:])
            time.sleep(2)
        return

    assert args.arch and args.shape, "--arch/--shape or --all"
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   scheme=args.scheme,
                   fsdp=None if args.fsdp is None else args.fsdp == "on",
                   remat=not args.no_remat,
                   hints=True if args.hints else None,
                   serve_sharded=args.serve_sharded)
    tag = (f"{args.arch}_{args.shape}_"
           f"{'2x16x16' if args.multi_pod else '16x16'}_{args.scheme}"
           + ("_hints" if (args.hints or os.environ.get('REPRO_SHARDING_HINTS') == '1') else "")
           + ("_sharded" if args.serve_sharded else ""))
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(res, f, indent=1)
    print(f"[dryrun] wrote {tag}.json")


if __name__ == "__main__":
    main()
