"""Serving cells: the program's ServeEngine behind EngineBridge and
CompletionFrontend on localhost, loaded by a child process over HTTP/SSE.

This process holds the chip(s). Set-up makes the weights on the device
from the seed (one jitted call, packed layer by layer), builds the engine
and runs one short request through it, so that every compiled step the
traffic uses exists before the window. Where the configuration's
`engine` names a mesh, `"mesh": {"data": D, "model": M}`, the engine is
the program's sharded one on `make_serve_mesh(D, M)`, and the weights are
made on their shards. The load generator (bench/lib/loadgen.py)
is a child process that never imports JAX; it sends the cell's schedule
and takes every timestamp on the client side.

The bench's own spans wrap the calls into the engine's layers
(`jax.profiler.TraceAnnotation`, prefix "bench."), and every forward the
engine dispatches is recorded with its chunk size, positions and active
rows: the counts behind the FLOP and byte models.
"""

from __future__ import annotations

import asyncio
import gc
import json
import math
import shutil
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import cell as C
from bench.lib import check_serve, gen, report, stats
from bench.lib import trace as T

LOADGEN = Path(__file__).with_name("loadgen.py")
LEAD_S = 1.0          # schedule start after the child is spawned
# how long past the window a request may finish: above the knee the backlog
# at the close takes a minute or more to drain, and an answer that comes
# late is late, not wrong (a run still ends well inside 360 s)
GRACE_S = 150.0


def arch_config(m: dict):
    """The program's ArchConfig from a configuration's `model` block: every
    key that names one of its fields (family, attn, mlp and the sizes)."""
    import dataclasses
    from repro.configs.base import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in m.items() if k in fields})


def serving_params(m: dict, seed: int, scheme: str, weights, mesh=None):
    """Serving weights made on the device in one jitted call by the
    configuration's weight module, each layer packed by the program's
    `prequantize` (a no-op for schemes that keep weights in floating
    point, whose GEMM weights, embedding and head are then served in
    weights.SERVED_DTYPE). The tree must be what
    `prequantize_specs(eval_shape(lm.init))` describes.

    With a mesh, each leaf is made on the shards that the program's
    `serve_param_shardings` gives it, so no device holds more than its
    share; the values are the same as without one (threefry is
    partitionable: each shard draws its own part of the same numbers)."""
    from repro.dist import sharding as SH
    from repro.models import lm
    from repro.serve.prequant import prequantize, prequantize_specs

    cfg = arch_config(m)
    dtype = weights.SERVED_DTYPE

    def cast(tree):
        """GEMM weights (not norm gains) kept in float32 go to dtype."""
        def one(path, x):
            if x.dtype != jnp.float32 or jax.tree_util.keystr(
                    path[-1:]) == "['g']":
                return x
            if isinstance(x, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(x.shape, dtype)
            return x.astype(dtype)
        return jax.tree_util.tree_map_with_path(one, tree)

    def pack(layer):
        one = {"stages": [{"l0": jax.tree.map(lambda x: x[None], layer)}]}
        packed = prequantize(one, cfg, scheme)["stages"][0]["l0"]
        return cast(jax.tree.map(lambda x: x[0], packed))

    # the tree is traced after the weights are made, and before them only
    # where a mesh needs their shardings: traced first, it raised the chat
    # cell's peak device memory by 5.7 MB on a v5e
    def specs():
        return cast(prequantize_specs(
            jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0))),
            cfg, scheme))

    params = weights.make_params(
        seed, m, pack, embed_dtype=dtype,
        out_shardings=None if mesh is None else SH.serve_param_shardings(
            specs(), mesh))
    if not same_tree(params, specs()):
        raise AssertionError("serving weights do not match the program's "
                             "prequantized tree")
    return params


def same_tree(a, b) -> bool:
    """Same pytree structure, leaf shapes and dtypes."""
    return jax.tree.structure(a) == jax.tree.structure(b) and all(
        x.shape == y.shape and x.dtype == y.dtype
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@dataclass
class Recorder:
    """Spans around the engine's layers and a record of every forward."""
    forwards: list = field(default_factory=list)  # (t, size, pos, active)

    def attach(self, eng) -> None:
        def span(name, fn):
            def wrapped(*a, **k):
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **k)
            return wrapped

        fwd = eng._forward

        def forward(size, tokens, pos, active):
            self.forwards.append((time.monotonic(), size, pos.copy(),
                                  active.copy()))
            name = "bench.forward.prefill" if size > 1 else \
                "bench.forward.step1"
            with jax.profiler.TraceAnnotation(name):
                return fwd(size, tokens, pos, active)

        eng._forward = forward
        eng.step = span("bench.engine.step", eng.step)
        eng._admit = span("bench.scheduler.admit", eng._admit)
        eng._prefill_tick = span("bench.engine.prefill_tick",
                                 eng._prefill_tick)
        eng._decode_tick = span("bench.engine.decode_tick", eng._decode_tick)


class Server:
    """Engine + bridge + frontend for one configuration and seed."""

    def __init__(self, config: dict, seed: int, prime=()):
        from repro.launch.mesh import make_serve_mesh
        from repro.obs.instrumentation import Instrumentation
        from repro.obs.metrics import MetricsRegistry
        from repro.serve.engine import EngineConfig, Request, ServeEngine
        from repro.serve.frontend import (CompletionFrontend, EngineBridge,
                                          FrontendConfig)

        m, e = config["model"], config["engine"]
        self.model = m
        self.cfg = arch_config(m)
        mesh = (make_serve_mesh(e["mesh"]["data"], e["mesh"]["model"])
                if "mesh" in e else None)
        self.params = serving_params(m, seed, e["scheme"],
                                     C.module(config["weights"]), mesh)
        self.obs = Instrumentation(registry=MetricsRegistry())
        self.engine = ServeEngine(self.cfg, self.params, EngineConfig(
            n_slots=e["n_slots"], max_len=e["max_len"],
            block_size=e["block_size"], n_blocks=e["n_blocks"],
            prefill_chunk=e["prefill_chunk"], scheme=e["scheme"],
            prequant=False, prefix_cache=e["prefix_cache"], mesh=mesh,
            obs=self.obs, clock=time.monotonic))
        self.rec = Recorder()
        self.rec.attach(self.engine)
        # warm-up: one prompt of a chunk plus one token, two new tokens,
        # compiles the (n_slots, chunk) and (n_slots, 1) steps and the
        # sampler; then the traffic's primed prompts (a shared context),
        # which stay in the prefix cache
        chunk = e["prefill_chunk"]
        for prompt in [list(range(1, chunk + 2))] + list(prime):
            self.engine.submit(Request(prompt=list(prompt), max_new=2))
            self.engine.run()
        jax.block_until_ready(self.engine.pool.caches)
        self.rec.forwards.clear()
        self.bridge = EngineBridge(self.engine).start()
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="frontend", daemon=True)
        self.thread.start()
        self.frontend = CompletionFrontend(self.bridge, FrontendConfig())
        asyncio.run_coroutine_threadsafe(self.frontend.start(),
                                         self.loop).result()

    def stats(self) -> dict:
        return self.bridge.call(lambda eng: dict(eng.stats)).result()

    def window(self, schedule: dict, seconds: float, out_dir: Path,
               trace_s: float = 0.0) -> dict:
        """Send `schedule` (bench/lib/gen.py) over a window of `seconds`;
        with trace_s > 0, profile that many seconds in the middle of it."""
        out_dir.mkdir(parents=True, exist_ok=True)
        t0 = time.monotonic() + LEAD_S
        sched = dict(schedule, port=self.frontend.port, t0=t0,
                     close=t0 + seconds, deadline=t0 + seconds + GRACE_S)
        (out_dir / "schedule.json").write_text(json.dumps(sched))
        res_path = out_dir / "client.json"
        res_path.unlink(missing_ok=True)
        with open(out_dir / "client.log", "w") as log:
            child = subprocess.Popen(
                [sys.executable, str(LOADGEN), str(out_dir / "schedule.json"),
                 str(res_path)], stdout=log, stderr=subprocess.STDOUT)
            try:
                time.sleep(max(t0 - time.monotonic(), 0.0))
                s0, n0 = self.stats(), len(self.rec.forwards)
                traced = None
                if trace_s > 0:
                    time.sleep(max(t0 + (seconds - trace_s) / 2
                                   - time.monotonic(), 0.0))
                    traced = self._trace(trace_s, out_dir / "trace")
                time.sleep(max(t0 + seconds - time.monotonic(), 0.0))
                s1, n1 = self.stats(), len(self.rec.forwards)
                child.wait(timeout=max(sched["deadline"] - time.monotonic(),
                                       0.0) + 30.0)
            finally:
                if child.poll() is None:
                    child.kill()
                    child.wait()
        traces = [t for t in self.obs.trace_sink.traces
                  if t.spans and t0 <= t.spans[0].t0 < t0 + seconds]
        tr = None
        if traced is not None:   # reduced once the load has finished
            tr = T.reduce_xplane(T.find_xplane(str(out_dir / "trace")))
            tr["host_t0"], tr["host_t1"] = traced
        return {"t0": t0, "seconds": seconds,
                "requests": schedule["requests"],
                "client": json.loads(res_path.read_text()),
                "stats": {k: s1[k] - s0[k] for k in s0},
                "forwards": self.rec.forwards[n0:n1], "traces": traces,
                "trace": tr}

    @staticmethod
    def _trace(trace_s: float, trace_dir: Path) -> tuple[float, float]:
        """Profile `trace_s` seconds into trace_dir; the host clock's
        (start, end) of the traced window."""
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        try:
            with jax.profiler.TraceAnnotation(T.WINDOW):
                t_start = time.monotonic()
                time.sleep(trace_s)
                t_end = time.monotonic()
        finally:
            jax.profiler.stop_trace()
        return t_start, t_end

    def close(self) -> None:
        asyncio.run_coroutine_threadsafe(self.frontend.stop(),
                                         self.loop).result()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join()
        self.bridge.stop()
        # drop the device state now: the reference runs in this process next
        self.engine.params = self.engine.pool.caches = None
        self.engine = self.bridge = self.frontend = self.params = None


# --------------------------------------------------------------------------
# end-to-end numbers from the client's records
# --------------------------------------------------------------------------

def client_numbers(win: dict) -> dict:
    t0, seconds = win["t0"], win["seconds"]
    ttft, itl, failed, in_window, late = [], [], 0, 0, []
    for r in win["client"]:
        req = win["requests"][r["i"]]
        late.append(r["sent"] - r["due"])
        ok = r["error"] is None and len(r["tokens"]) == req["max_tokens"]
        if not ok:
            failed += 1
            last = r["times"][-1] - t0 - seconds if r["times"] else None
            sys.stderr.write(
                f"failed request {r['i']}: due {r['due'] - t0:.2f} s into "
                f"the window, prompt {len(req['prompt'])}, "
                f"{len(r['tokens'])} of {req['max_tokens']} tokens, last "
                f"token {last} s past the close, error {r['error']}\n")
        ttft.append(r["times"][0] - r["due"] if ok else math.inf)
        if ok:
            itl += list(np.diff(r["times"]))
        in_window += sum(t0 <= t <= t0 + seconds for t in r["times"])
    return {"attempted": len(win["client"]), "failed": failed,
            "ttft_s": ttft, "itl_s": itl,
            "output_tokens_in_window": in_window,
            "lateness_s": {"p50": stats.percentile(late, 50),
                           "p99": stats.percentile(late, 99),
                           "max": max(late)}}


def end_to_end(nums: dict, seconds: float) -> dict:
    def finite(x):
        return x if math.isfinite(x) else None
    return {"ttft_p90_ms": finite(1e3 * stats.percentile(nums["ttft_s"], 90)),
            "itl_p99_ms": (1e3 * stats.percentile(nums["itl_s"], 99)
                           if nums["itl_s"] else None),
            "output_tok_s": nums["output_tokens_in_window"] / seconds}


def check_sample(win: dict, seed: int, served_tokens: int) -> list:
    """Finished requests to compare: the longest, then others drawn from
    the seed, until `served_tokens` served tokens are in the sample."""
    reqs = win["requests"]
    done = [(reqs[r["i"]]["prompt"], r["tokens"]) for r in win["client"]
            if r["error"] is None
            and len(r["tokens"]) == reqs[r["i"]]["max_tokens"]]
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i][0]) + len(done[i][1])))
    rest = order[1:]
    gen.rng(seed, 7).shuffle(rest)
    picked, n = [], 0
    for i in [order[0]] + rest:
        if n >= served_tokens:
            break
        picked.append(done[i])
        n += len(done[i][1])
    return picked


# --------------------------------------------------------------------------
# one run of a serving cell
# --------------------------------------------------------------------------

def run(cell, seed: int, seconds: float, traced: bool, out_dir: Path,
        process_start: float) -> dict:
    m, tf = cell.config["model"], cell.traffic
    schedule = gen.make(tf, seconds, seed, m["vocab"])
    server = Server(cell.config, seed, prime=schedule["prime"])
    setup_s = time.monotonic() - process_start
    win = server.window(schedule, seconds, out_dir,
                        trace_s=T.TRACE_S if traced else 0.0)
    server.close()
    del server
    gc.collect()
    device = report.device(jax.devices()[:cell.chips])
    nums = client_numbers(win)
    sys.stderr.write(f"generator lateness (s): {json.dumps(nums['lateness_s'])}\n")
    (out_dir / "lateness.json").write_text(json.dumps(nums["lateness_s"]))
    sample = check_sample(win, seed, tf["check"]["served_tokens"])
    check = check_serve.compare(C.module(cell.config["reference"]), m, seed,
                                sample, tf["check"], nums["failed"],
                                check_serve.model_kw(cell.config))
    if traced:
        ctx = {"model": m, "window": win, "peaks": cell.peaks,
               "chips": cell.chips}
        metrics = {}
        for spec in cell.per_layer:
            v = cell.reader(spec["name"])(ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        e2e = end_to_end(nums, seconds)
        e2e["setup_s"] = setup_s
        metrics = {s["name"]: {"value": e2e[s["name"]], "unit": s["unit"]}
                   for s in cell.end_to_end if e2e.get(s["name"]) is not None}
    return {"correct": check["correct"], "attempted": nums["attempted"],
            "failed": nums["failed"], "metrics": metrics, "device": device,
            "trace": win["trace"], "check": check["numbers"]}
