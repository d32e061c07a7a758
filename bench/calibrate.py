#!/usr/bin/env python3
"""Readings that set a cell's rate, batch and limits; run on the chip by
hand, never by the benchmark's own runs.

    python3 bench/calibrate.py knee --workload W --seed N --rates 0.5,1,2 --seconds 30
    python3 bench/calibrate.py serve-check --workload W --seeds 1,2,3 --seconds 20 [--control fp8] [--config F]
    python3 bench/calibrate.py train-mem --workload W --batches 2,4,6
    python3 bench/calibrate.py train-check --workload W --seeds 1,2,3 [--control bfloat16] [--fault half_batch]

Each prints one JSON line per reading. `serve-check` runs the program on
each seed at the cell's own load for a short window and reads the gap of
the served tokens; with --control it also reads the gap of the tokens that
the reference computed at that lower precision puts first on the same
prompts and tokens. `train-check` reads the program's first steps against
the reference; with --control, the reference at that precision against the
reference; with --fault, the program with that fault planted. `knee` and
`serve-check` build the cell's server as a benchmark run does
(lib/serve.py `Server`), on its mesh where the configuration names one.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.run import OUT, configure_cache  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def saturated(nums: dict) -> bool:
    """A backlog that grows through the window: a request failed or never
    finished, or the last third of the requests waited for their first
    token over twice as long as the first third (plus half a second)."""
    ttft = nums["ttft_s"]
    third = max(len(ttft) // 3, 1)
    first, last = sum(ttft[:third]) / third, sum(ttft[-third:]) / third
    return nums["failed"] > 0 or last > 2 * first + 0.5


def knee(cell, args) -> None:
    """The rates in --rates, lowest first, each for --seconds on one
    server, until one saturates: the knee is the highest before it."""
    from bench.lib import gen, serve, stats
    m = cell.config["model"]
    server = serve.Server(cell.config, args.seed, prime=gen.make(
        cell.traffic, args.seconds, args.seed, m["vocab"])["prime"])
    import jax
    emit(phase="setup", seconds=time.monotonic() - PROCESS_START,
         memory=[d.memory_stats() for d in jax.devices()[:cell.chips]])
    for rate in [float(r) for r in args.rates.split(",")]:
        tf = dict(cell.traffic, arrival={"process": "poisson",
                                         "rate_rps": rate})
        sched = gen.make(tf, args.seconds, args.seed, m["vocab"])
        win = server.window(sched, args.seconds, OUT / "knee")
        nums = serve.client_numbers(win)
        ok = [x for x in nums["ttft_s"] if x < float("inf")]
        third = max(len(ok) // 3, 1)
        s = win["stats"]
        emit(rate=rate, attempted=nums["attempted"], failed=nums["failed"],
             ttft_p50_ms=1e3 * stats.percentile(ok, 50) if ok else None,
             ttft_p90_ms=1e3 * stats.percentile(ok, 90) if ok else None,
             ttft_first_third_ms=1e3 * sum(ok[:third]) / third if ok else None,
             ttft_last_third_ms=1e3 * sum(ok[-third:]) / third if ok else None,
             e2e=serve.end_to_end(nums, args.seconds),
             decode_batch_mean=s["decode_tokens"] / max(s["decode_steps"], 1),
             prefill_step_ms=1e3 * s["prefill_s"] / max(s["prefill_steps"], 1),
             decode_step_ms=1e3 * s["decode_s"] / max(s["decode_steps"], 1),
             ticks=s["ticks"], lateness=nums["lateness_s"],
             saturated=saturated(nums))
        if saturated(nums):
            break       # past the knee: higher rates only queue longer
        time.sleep(2.0)
    server.close()


def serve_check(cell, args) -> None:
    import jax
    import numpy as np
    from bench.lib import cell as C
    from bench.lib import check_serve, gen, serve
    m, tf = cell.config["model"], cell.traffic
    ref = C.module(cell.config["reference"])
    kw = check_serve.model_kw(cell.config)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        sched = gen.make(tf, args.seconds, seed, m["vocab"])
        server = serve.Server(cell.config, seed, prime=sched["prime"])
        win = server.window(sched, args.seconds, OUT / "serve-check",
                            trace_s=args.trace_s)
        server.close()
        del server
        gc.collect()
        nums = serve.client_numbers(win)
        sample = serve.check_sample(win, seed, tf["check"]["served_tokens"])
        g = check_serve.gaps(ref, m, seed, sample, kw)
        out = {"seed": seed, "failed": nums["failed"],
               "attempted": nums["attempted"], "served": int(g.size),
               "gap_max": float(g.max()), "gap_mean": float(g.mean()),
               "share_gap0": float(np.mean(g == 0)),
               "seconds": time.monotonic() - t0}
        if args.control:
            choice = check_serve.control_choice(ref, m, seed, sample, kw,
                                                args.control)
            c = check_serve.gaps(ref, m, seed, sample, kw, chosen=choice)
            out.update(control=args.control, control_gap_max=float(c.max()),
                       control_gap_mean=float(c.mean()),
                       control_share_gap0=float(np.mean(c == 0)))
        if win["trace"] is not None and args.describe:
            from bench.lib import trace as T
            desc = T.describe(T.find_xplane(str(OUT / "serve-check" / "trace")))
            (OUT / "describe.json").write_text(json.dumps(desc, indent=1))
            out["trace_ops"] = len(win["trace"]["device_ops"])
        emit(**out)
        jax.clear_caches()


def train_mem(cell, args) -> None:
    import jax
    from bench.lib import serve
    from repro.models import lm
    from repro.train.train_step import make_train_step
    m, t = cell.config["model"], cell.config["trainer"]
    cfg = serve.arch_config(m)
    init_state, train_step = make_train_step(cfg, t["scheme"])
    state = jax.eval_shape(lambda: init_state(
        lm.init(cfg, jax.random.PRNGKey(0))))
    for b in [int(x) for x in args.batches.split(",")]:
        batch = {k: jax.ShapeDtypeStruct((b, t["seq"]), "int32")
                 for k in ("tokens", "labels")}
        ma = jax.jit(train_step, donate_argnums=(0,)).lower(
            state, batch).compile().memory_analysis()
        emit(batch=b, argument=ma.argument_size_in_bytes,
             output=ma.output_size_in_bytes, temp=ma.temp_size_in_bytes,
             alias=ma.alias_size_in_bytes,
             total=ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def half_batch(train_step):
    """Fault: the step sees only the first half of the batch's rows and
    takes the mean over them."""
    def step(state, batch):
        half = batch["tokens"].shape[0] // 2
        return train_step(state, {k: v[:half] for k, v in batch.items()})
    return step


def state_unchanged(train_step):
    """Fault: the step computes as usual and returns the state it was
    given."""
    def step(state, batch):
        _, metrics = train_step(state, batch)
        return state, metrics
    return step


FAULTS = {"half_batch": half_batch, "state_unchanged": state_unchanged}


def train_check(cell, args) -> None:
    import jax
    from bench.lib import cell as C
    from bench.lib import check_train, train
    m, t = cell.config["model"], cell.config["trainer"]
    ref_train = C.module(cell.config["reference"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        tr = train.Trainer(cell.config, seed,
                           fault=FAULTS[args.fault] if args.fault else None)
        prog = tr.check_steps()
        batches = tr.batches(train.CHECK_STEPS)
        del tr
        gc.collect()
        ref = ref_train.run(m, seed, t, batches)
        out = {"seed": seed, "fault": args.fault,
               **{k: v for k, v in check_train.numbers(prog, ref).items()},
               "loss": prog["loss"], "ref_loss": ref["loss"]}
        if args.control:
            ctl = ref_train.run(m, seed, t, batches, dtype=args.control)
            out["control"] = {k: v for k, v in
                              check_train.numbers(ctl, ref).items()}
        out["seconds"] = time.monotonic() - t0
        emit(**out)
        jax.clear_caches()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["knee", "serve-check", "train-mem",
                                     "train-check"])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="1")
    ap.add_argument("--batches", default="4")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace-s", type=float, default=0.0)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--config", default=None)
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None, choices=sorted(FAULTS))
    args = ap.parse_args()
    import jax
    from bench.lib import cell as C
    configure_cache(jax)
    cell = C.load(ROOT, args.workload)
    if args.config:     # read another configuration under this cell's mix
        cell.config = json.loads(Path(args.config).read_text())
    cell.mesh_shape()   # a serving mesh has to take the cell's chips
    emit(device=jax.devices()[0].device_kind, count=len(jax.devices()),
         memory=(jax.devices()[0].memory_stats() or {}).get("bytes_limit"))
    {"knee": knee, "serve-check": serve_check, "train-mem": train_mem,
     "train-check": train_check}[args.mode](cell, args)


if __name__ == "__main__":
    main()
