"""Cells cut to CPU size for the harness's tests: the real cell's files,
with the model's sizes, the engine's slots and the traffic shrunk, and
for a serving cell optionally a (data, model) mesh over as many of the
CPU devices (conftest.py)."""

import copy
import time
from pathlib import Path

from bench.lib import cell as C

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
SMALL = dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
             d_ff=256, vocab=512)


def cell(workload: str, mesh: dict | None = None, **model) -> C.Cell:
    c = C.load(ROOT, workload)
    cfg = copy.deepcopy(c.config)
    cfg["model"].update(SMALL, **model)
    if mesh is not None:
        cfg["engine"]["mesh"] = mesh
        c.chips = mesh["data"] * mesh["model"]
    if "engine" in cfg:
        cfg["engine"].update(n_slots=4, max_len=256, n_blocks=64)
        c.traffic = dict(
            c.traffic, arrival={"process": "poisson", "rate_rps": 2.0},
            prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.8,
                        "min": 8, "max": 64},
            output_len={"dist": "lognormal", "median": 8, "sigma": 0.8,
                        "min": 4, "max": 16},
            check=dict(c.traffic["check"], served_tokens=20))
    else:
        cfg["trainer"].update(seq=128, batch=4)
    c.config = cfg
    c.peaks = PEAKS
    return c


def run(c: C.Cell, seed: int, out: Path, seconds: float = 4.0) -> dict:
    if "engine" in c.config:
        from bench.lib import serve as runner
    else:
        from bench.lib import train as runner
    return runner.run(c, seed, seconds, False, out, time.monotonic())
