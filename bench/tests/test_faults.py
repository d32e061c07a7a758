"""A run with the timed path broken underneath reads `correct` false.

Each test skips only the harness's look for a chip: it drives the rest of
a run (set-up, the window over HTTP or the train loop, the reference
comparison) on a CPU-sized cut of the cell, with one fault planted in the
program. A sound run of the same cut is checked to read `correct` true.
The serving tests run on one device and on a (data=2, model=2) mesh of
four CPU devices, the program's sharded engine.
"""

import pytest

from bench.tests import tiny

SERVE = "granite-8b-bf16.chat"
TRAIN = "yi-9b-train.seq2k"
MESHES = pytest.mark.parametrize("mesh", [None, {"data": 2, "model": 2}],
                                 ids=["one_device", "mesh_2x2"])


@MESHES
def test_serve_sound(tmp_path, monkeypatch, mesh):
    """Correct, on the sharded engine where the configuration names a
    mesh, with a memory reading for each of the cell's chips."""
    from repro.serve.engine import ServeEngine
    init, shards = ServeEngine.__init__, []

    def spy(self, *a, **k):
        init(self, *a, **k)
        shards.append(None if self.mesh is None else self.data_shards)

    monkeypatch.setattr(ServeEngine, "__init__", spy)
    c = tiny.cell(SERVE, mesh=mesh)
    res = tiny.run(c, 2**31 + 3, tmp_path)
    assert res["correct"], res["check"]
    assert shards == [None if mesh is None else mesh["data"]]
    per_chip = res["device"]["memory_peak_bytes_per_chip"]
    assert len(per_chip) == res["device"]["count"] == c.chips
    assert res["device"]["memory_peak_bytes"] == max(per_chip)


def test_serve_closed_loop_sound(tmp_path):
    """Closed-loop clients over a shared context primed in set-up: every
    request sent is finished and matches the reference."""
    c = tiny.cell(SERVE)
    c.traffic = {"kind": "closed_loop", "clients": 3,
                 "requests_per_client": 2, "shared_prefix": 32,
                 "prompt_len": {"dist": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 4, "max": 16},
                 "output_len": {"dist": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 4, "max": 16},
                 "check": dict(c.traffic["check"], served_tokens=20)}
    res = tiny.run(c, 2**31 + 4, tmp_path)
    assert res["correct"] and res["attempted"] >= 3, res


@MESHES
def test_serve_token_altered(tmp_path, monkeypatch, mesh):
    """Every sampled token is replaced by its neighbour id."""
    from repro.serve.engine import ServeEngine
    sample = ServeEngine._sample

    def altered(self, logits):
        return (sample(self, logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    res = tiny.run(tiny.cell(SERVE, mesh=mesh), 2**31 + 3, tmp_path)
    assert not res["correct"], res["check"]


def test_serve_step_keeps_cache(tmp_path, monkeypatch):
    """The compiled step returns the KV pool it was given: no token's K/V
    is ever written."""
    from repro.serve import decode
    make = decode.make_paged_serve_step

    def stale(*a, **k):
        step = make(*a, **k)

        def run(params, caches, *rest):
            logits, _ = step(params, caches, *rest)
            return logits, caches
        return run

    monkeypatch.setattr(decode, "make_paged_serve_step", stale)
    res = tiny.run(tiny.cell(SERVE), 2**31 + 3, tmp_path)
    assert not res["correct"], res["check"]


def test_train_sound(tmp_path):
    res = tiny.run(tiny.cell(TRAIN), 2**31 + 5, tmp_path, seconds=2.0)
    assert res["correct"], res["check"]


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_train_fault(tmp_path, monkeypatch, fault):
    from bench import calibrate
    from bench.lib import train
    init = train.Trainer.__init__

    def planted(self, config, seed, fault_fn=None):
        init(self, config, seed, fault=calibrate.FAULTS[fault])

    monkeypatch.setattr(train.Trainer, "__init__", planted)
    res = tiny.run(tiny.cell(TRAIN), 2**31 + 5, tmp_path, seconds=2.0)
    assert not res["correct"], res["check"]
