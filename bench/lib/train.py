"""Training cells: quartet2 pre-training steps through the program's
`make_train_step` under `jax.jit`, fed by its `SyntheticCorpus`, in the
loop the `Trainer` runs (feed, step, a sync on the loss) without the
checkpoints: a checkpoint of the state would write gigabytes per run.
In the window the loss of a step is read AHEAD_S seconds of steps late,
so that the chip stays fed while the host stands still.

Set-up builds one object, the compiled step with its state, and drives it
from the seed through its first CHECK_STEPS steps with the window's own
call and feed; those steps are what the reference follows. The window
then continues the same state.
"""

from __future__ import annotations

import collections
import gc
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from bench.lib import cell as C
from bench.lib import check_train, report
from bench.lib import trace as T
from bench.lib.serve import arch_config, same_tree

CHECK_STEPS = 3
AHEAD_S = 4.0       # seconds of steps dispatched ahead of the loss read


def corpus_seed(seed: int) -> int:
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


class Trainer:
    """The compiled step, its state and its feed for one seed."""

    def __init__(self, config: dict, seed: int, fault=None):
        from repro.data.pipeline import DataConfig, SyntheticCorpus
        from repro.models import lm
        from repro.train.train_step import make_train_step

        m, t = config["model"], config["trainer"]
        self.m, self.t, self.seed = m, t, seed
        self.weights = C.module(config["weights"])
        cfg = arch_config(m)
        corpus = SyntheticCorpus(DataConfig(
            vocab=m["vocab"], seq_len=t["seq"], global_batch=t["batch"],
            seed=corpus_seed(seed)))
        self.feed = jax.jit(lambda s: corpus.batch_at(s))
        init_state, train_step = make_train_step(
            cfg, t["scheme"], base_lr=t["base_lr"],
            total_steps=t["total_steps"], weight_decay=t["weight_decay"],
            grad_clip=t["grad_clip"])
        params = self.weights.make_params(seed, m)
        want = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
        if not same_tree(params, want):
            raise AssertionError("weights do not match the program's tree")
        self.state = jax.jit(init_state)(params)
        del params
        step = fault(train_step) if fault is not None else train_step
        self.step_fn = jax.jit(step, donate_argnums=(0,))
        self.step = 0

    def dispatch(self):
        """Feed and step, without waiting: the step's loss, on the device."""
        with jax.profiler.TraceAnnotation("bench.train.feed"):
            batch = self.feed(self.step)
        with jax.profiler.TraceAnnotation("bench.train.step"):
            self.state, met = self.step_fn(self.state, batch)
        self.step += 1
        return met["loss"]

    @staticmethod
    def sync(loss) -> float:
        with jax.profiler.TraceAnnotation("bench.train.sync"):
            return float(loss)

    def advance(self) -> float:
        return self.sync(self.dispatch())

    def check_steps(self) -> dict:
        """Drive the first steps; read what the reference is compared on.
        Also times the last of them (`step_s`)."""
        losses = [self.advance()]
        beta1 = 0.9
        grad = check_train.leaf_norms(jax.tree.map(
            lambda mu: mu / (1 - beta1), self.state.opt.mu))
        losses += [self.advance() for _ in range(CHECK_STEPS - 2)]
        t = time.monotonic()
        losses.append(self.advance())
        self.step_s = time.monotonic() - t
        p0 = self.weights.make_params(self.seed, self.m)
        change = check_train.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b, self.state.params, p0))
        del p0
        return {"loss": losses, "grad": grad, "change": change}

    def batches(self, n: int) -> list:
        return [self.feed(i) for i in range(n)]


def window(tr: Trainer, seconds: float, trace_s: float, trace_dir: Path):
    """Steps dispatched until `seconds` have passed, each step's loss read
    once AHEAD_S seconds of later steps are in flight; then the window
    waits for every step sent and ends after that wait. With trace_s > 0
    the middle trace_s seconds are profiled."""
    ahead = max(1, round(AHEAD_S / tr.step_s))
    pending = collections.deque()
    t0 = time.monotonic()
    start, n, red, ann = t0 + (seconds - trace_s) / 2, 0, None, None
    tr_t0 = None
    while time.monotonic() - t0 < seconds:
        now = time.monotonic()
        if trace_s > 0 and ann is None and tr_t0 is None and now >= start:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            ann = jax.profiler.TraceAnnotation(T.WINDOW)
            ann.__enter__()
            tr_t0 = time.monotonic()
        if ann is not None and now >= tr_t0 + trace_s:
            ann.__exit__(None, None, None)
            ann = None
            jax.profiler.stop_trace()
        pending.append(tr.dispatch())
        n += 1
        if len(pending) > ahead:
            tr.sync(pending.popleft())
    while pending:
        tr.sync(pending.popleft())
    end = time.monotonic()
    if ann is not None:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if tr_t0 is not None:   # reduced once the window has closed
        red = T.reduce_xplane(T.find_xplane(str(trace_dir)))
    return n, end - t0, red


def run(cell, seed: int, seconds: float, traced: bool, out_dir: Path,
        process_start: float) -> dict:
    m, t = cell.config["model"], cell.config["trainer"]
    out_dir.mkdir(parents=True, exist_ok=True)
    tr = Trainer(cell.config, seed)
    prog = tr.check_steps()
    setup_s = time.monotonic() - process_start
    n, window_s, red = window(tr, seconds, T.TRACE_S if traced else 0.0,
                              out_dir / "trace")
    device = report.device(jax.devices()[:cell.chips])
    batches = tr.batches(CHECK_STEPS)
    del tr
    gc.collect()
    ref = C.module(cell.config["reference"]).run(m, seed, t, batches)
    check = check_train.compare(prog, ref, cell.traffic["check"])
    tok_s = n * t["batch"] * t["seq"] / window_s
    if traced:
        ctx = {"model": m, "trainer": t, "train_tok_s": tok_s,
               "trace": red, "peaks": cell.peaks, "chips": cell.chips}
        metrics = {}
        for spec in cell.per_layer:
            v = cell.reader(spec["name"])(ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        e2e = {"train_tok_s": tok_s, "setup_s": setup_s}
        metrics = {s["name"]: {"value": e2e[s["name"]], "unit": s["unit"]}
                   for s in cell.end_to_end}
    return {"correct": check["correct"], "attempted": n, "failed": 0,
            "metrics": metrics, "device": device, "trace": red,
            "check": check["numbers"], "detail": check["detail"]}
