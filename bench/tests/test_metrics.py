"""Per-layer readers on hand-built contexts: the value where there is
something to read, None where there is not."""

import numpy as np
import pytest

from bench.lib import flops, report
from bench.tests import tiny

M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 16, "d_ff": 128, "vocab": 100}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}


class _Trace:
    def __init__(self, wait):
        self.queue_wait_s = wait


def _reader(name):
    return tiny.cell("granite-8b-bf16.chat").reader(name)


def _serve_ctx(trace=None):
    stats = {"prefill_s": 0.5, "prefill_steps": 10, "decode_s": 2.0,
             "decode_steps": 100, "decode_tokens": 750, "prefill_tokens": 150,
             "prefill_skipped_tokens": 50}
    fw = [(1.0, 16, np.array([0, 0]), np.array([True, False])),
          (2.0, 1, np.array([16, 3]), np.array([True, True]))]
    return {"model": M, "peaks": PEAKS, "chips": 1,
            "window": {"stats": stats, "forwards": fw, "seconds": 4.0,
                       "traces": [_Trace(w) for w in (0.1, 0.2, 0.3, 0.4)],
                       "trace": trace}}


def _trace():
    return {"window_s": 2.0, "chips": 1, "host_t0": 0.5, "host_t1": 2.5,
            "device_ops": [["fusion.1", 0.0, 0.5, 0],
                           ["%paged_attention.3 = f32[4,1,4,16] custom-call()", 0.6, 0.1, 0],
                           ["fusion.2", 1.5, 0.25, 0]],
            "host_spans": [["bench.engine.step", 0.0, 2.0],
                           ["bench.scheduler.admit", 0.7, 0.6]]}


def test_counter_readers():
    ctx = _serve_ctx()
    assert _reader("prefill_step_ms")(ctx) == pytest.approx(50.0)
    assert _reader("decode_step_ms")(ctx) == pytest.approx(20.0)
    assert _reader("decode_batch_mean")(ctx) == pytest.approx(7.5)
    assert _reader("prefix_hit_share")(ctx) == pytest.approx(25.0)
    assert _reader("queue_wait_p90_ms")(ctx) == pytest.approx(370.0)


def test_serve_mfu_counts_active_rows():
    want = (sum(flops.serve_token_flops(M, c) for c in range(1, 17))
            + flops.serve_token_flops(M, 17) + flops.serve_token_flops(M, 4))
    assert _reader("serve_mfu")(_serve_ctx()) == \
        pytest.approx(100 * want / (4.0 * 1e12))


def test_serve_mfu_over_the_cells_chips():
    one = _reader("serve_mfu")(_serve_ctx())
    assert _reader("serve_mfu")(dict(_serve_ctx(), chips=4)) == \
        pytest.approx(one / 4)


class _Device:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return {"peak_bytes_in_use": self.peak}


@pytest.mark.parametrize("peaks", [[5], [5, 9, 7, 6]])
def test_device_record_reads_every_chip(peaks):
    d = report.device([_Device(p) for p in peaks])
    assert d["count"] == len(peaks)
    assert d["memory_peak_bytes_per_chip"] == peaks
    assert d["memory_peak_bytes"] == max(peaks)


def test_trace_readers():
    ctx = _serve_ctx(_trace())
    assert _reader("device_idle_share.serve")(ctx) == \
        pytest.approx(100 * (1 - 0.85 / 2.0))
    works = [flops.paged_attention_work(M, 16, [0, 0], [True, False]),
             flops.paged_attention_work(M, 1, [16, 3], [True, True])]
    least = max(sum(w[0] for w in works) / 1e12,
                sum(w[1] for w in works) / 1e9)
    assert _reader("paged_attn_roofline")(ctx) == \
        pytest.approx(100 * least / 0.1)


def test_roofline_finds_the_kernel_on_a_mesh():
    """On a mesh XLA names the kernel's op after the shard_map it was
    inlined from; its JAX name stack still ends in the kernel's jit and
    its pallas_call. Any other op under that jit is not the kernel."""
    one = _reader("paged_attn_roofline")(_serve_ctx(_trace()))
    tr = _trace()
    op = "%shard_map.217 = f32[8,1,32,128] custom-call()"
    tr["device_ops"][1][0] = op
    stack = ("jit(paged_serve_step)/shard_map/while/body/closed_call/"
             "jit(paged_attention)/shard_map/")
    tr["op_scopes"] = {op: stack + "pallas_call"}
    assert _reader("paged_attn_roofline")(_serve_ctx(tr)) == \
        pytest.approx(one)
    tr["op_scopes"] = {op: stack + "reshape"}
    assert _reader("paged_attn_roofline")(_serve_ctx(tr)) is None


def test_readers_find_nothing():
    ctx = _serve_ctx(None)
    assert _reader("paged_attn_roofline")(ctx) is None
    assert _reader("device_idle_share.serve")(ctx) is None
    ctx["window"]["stats"] = dict.fromkeys(ctx["window"]["stats"], 0)
    ctx["window"]["traces"] = []
    for name in ("prefill_step_ms", "decode_step_ms", "decode_batch_mean",
                 "prefix_hit_share", "queue_wait_p90_ms"):
        assert _reader(name)(ctx) is None


def test_breakdown_labels_gaps_by_innermost_span():
    b = report.breakdown(_trace())
    assert b["device_ops"][0] == ["fusion.1", 0.5]
    labels = dict((round(s, 6), n) for n, s in b["idle_gaps"])
    assert labels[0.8] == "scheduler.admit"     # 0.7 .. 1.5
    assert labels[0.25] == "engine.step"        # 1.75 .. 2.0
    assert labels[0.1] == "engine.step"         # 0.5 .. 0.6
    assert report.busy_s(_trace()) == pytest.approx(0.85)


def test_train_mfu():
    yi = tiny.cell("yi-9b-train.seq2k")
    ctx = {"model": M, "trainer": {"seq": 128}, "train_tok_s": 1000.0,
           "peaks": PEAKS, "trace": None, "chips": 1}
    assert yi.reader("train_mfu")(ctx) == pytest.approx(
        100 * 1000.0 * flops.train_token_flops(M, 128) / 1e12)
    assert yi.reader("train_mfu")(dict(ctx, chips=4)) == pytest.approx(
        100 * 1000.0 * flops.train_token_flops(M, 128) / 4e12)
    assert yi.reader("device_idle_share.train")(ctx) is None
