"""The trace reduction on a small trace recorded on a v5e (the tiny serving
cut of granite-8b-bf16.chat, 1 s of its window)."""

from pathlib import Path

import pytest

from bench.lib import report, trace

DATA = Path(__file__).with_name("data") / "serve_small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_xplane(str(DATA))


def test_window_and_device_ops(red):
    assert red["chips"] == 1
    assert 0.1 < red["window_s"] < 1.5
    ops = red["device_ops"]
    assert ops and all(len(o) == 4 and o[3] == 0 for o in ops)
    # every op overlaps the window; times are relative to its start
    assert all(s + d > 0 and s < red["window_s"] for _, s, d, _ in ops)
    assert [o[1] for o in ops] == sorted(o[1] for o in ops)


def test_host_spans_are_the_bench_annotations(red):
    names = {h[0] for h in red["host_spans"]}
    assert "bench.engine.step" in names
    assert names <= {"bench.engine.step", "bench.scheduler.admit",
                     "bench.engine.prefill_tick", "bench.engine.decode_tick",
                     "bench.forward.prefill", "bench.forward.step1"}


def test_kernel_found_and_busy_bounded(red):
    from bench.tests import tiny
    is_kernel = tiny.cell("granite-8b-bf16.chat").reader(
        "paged_attn_roofline").__globals__["is_kernel"]
    assert any(is_kernel(n) for n, _, _, _ in red["device_ops"])
    busy = report.busy_s(red)
    assert 0 < busy <= red["window_s"]
    b = report.breakdown(red)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert not any(n.startswith("while") for n, _ in b["device_ops"])
    assert sum(s for _, s in b["idle_gaps"]) <= red["window_s"] - busy + 1e-9


def test_describe_names_the_planes():
    desc = trace.describe(str(DATA))
    assert "XLA Ops" in desc["/device:TPU:0"]
