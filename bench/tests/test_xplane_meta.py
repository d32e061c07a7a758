"""The program's spans and scopes read from profiler traces: the wire-format
reader on traces recorded on a v5e, and the two readers that use it
(tick_host_ms, quant_share.train) on recorded and hand-built traces."""

import shutil
from pathlib import Path

import pytest

from bench.lib import report, trace, xplane_meta
from bench.tests import tiny

DATA = Path(__file__).with_name("data")
OLD = DATA / "serve_small.xplane.pb"           # recorded before the spans
# recorded on a v5e with the program's spans and scopes (profiler without
# its Python tracer): the tiny chat cut at 8 req/s, 1 s of its window; the
# tiny train cut, one step, its /host:metadata plane (the modules' HLO
# protos, 8.2 MB, read by nothing here) dropped to keep the file small
SERVE = DATA / "serve_spans.xplane.pb"
TRAIN = DATA / "train_scopes.xplane.pb"


def _reader(workload, name):
    return tiny.cell(workload).reader(name)


def _full(path):
    tr = trace.reduce_xplane(str(path))
    return tr, xplane_meta.extend(tr, str(path))


@pytest.fixture(scope="module")
def serve_tr():
    return _full(SERVE)[1]


@pytest.fixture(scope="module")
def train_tr():
    return _full(TRAIN)[1]


def test_op_scopes_of_a_trace_without_program_scopes():
    scopes, clashes = xplane_meta.op_scopes(str(OLD))
    assert len(scopes) >= 100 and clashes == 0
    pa = [p for op, p in scopes.items()
          if report.short_name(op) == "paged_attention.8"]
    assert pa and pa[0].endswith("/pallas_call")
    assert pa[0].startswith("jit(paged_serve_step)/")


def test_extend_keeps_the_reduction():
    tr, full = _full(OLD)
    assert {k: full[k] for k in tr} == tr
    assert full["program_spans"] == []          # no serve.* spans then
    assert set(full) - set(tr) == {"program_spans", "op_scopes",
                                   "op_scope_clashes"}


def test_readers_find_nothing_without_spans_or_scopes():
    full = _full(OLD)[1]
    assert _reader("granite-8b-bf16.chat", "tick_host_ms")(
        {"window": {"trace": full}}) is None
    assert _reader("yi-9b-train.seq2k", "quant_share.train")(
        {"trace": full}) is None
    assert _reader("granite-8b-bf16.chat", "tick_host_ms")(
        {"window": {"trace": None}}) is None
    assert _reader("yi-9b-train.seq2k", "quant_share.train")(
        {"trace": None}) is None


def test_serve_spans_nest_on_the_engine_thread(serve_tr):
    spans = serve_tr["program_spans"]
    names = {n for n, _, _, _ in spans}
    assert {"serve.tick", "serve.admit", "serve.decode", "serve.sync",
            "serve.dispatch", "serve.readback", "serve.flush",
            "serve.bridge.commands"} <= names
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len({th for _, _, _, th in ticks}) == 1
    engine = ticks[0][3]
    # a span is recorded only if it opened and closed inside the session,
    # so the ticks cut by the session's edges are missing: look between
    first, last = ticks[0][1], max(t + td for _, t, td, _ in ticks)
    syncs = [(s, d, th) for n, s, d, th in spans
             if n == "serve.sync" and first <= s and s + d <= last]
    assert syncs
    for s, d, th in syncs:
        assert th == engine
        assert any(t <= s and s + d <= t + td for _, t, td, _ in ticks)


def test_tick_host_ms_on_recorded_trace(serve_tr):
    host = xplane_meta.tick_host_s(serve_tr)
    assert host and all(x >= 0 for x in host)
    v = _reader("granite-8b-bf16.chat", "tick_host_ms")(
        {"window": {"trace": serve_tr}})
    assert v == pytest.approx(1e3 * sum(host) / len(host))
    # a tick is host time plus its syncs: no more than the tick itself
    ticks = [d for n, s, d, _ in serve_tr["program_spans"]
             if n == "serve.tick" and s >= 0 and s + d <= serve_tr["window_s"]]
    assert 0 < v <= 1e3 * max(ticks)


def test_idle_by_span_covers_the_idle_time(serve_tr):
    by = xplane_meta.idle_by_span(serve_tr)
    idle = serve_tr["window_s"] - report.busy_s(serve_tr)
    assert sum(by.values()) == pytest.approx(idle)
    assert set(by) - {"outside any span"} <= {
        n for n, _, _, _ in serve_tr["program_spans"]}


def test_train_trace_scopes(train_tr):
    paths = train_tr["op_scopes"]
    found = {s for p in paths.values() for s in xplane_meta.scopes_on(p)}
    assert set(xplane_meta.SCOPES) <= found
    assert any("transpose(" in p and "quant.bwd" in p for p in paths.values())
    by = xplane_meta.device_s_by_scope(train_tr)
    assert {"gemm.fwd", "gemm.bwd", "quant.fwd", "quant.bwd"} <= set(by)
    # ops of one line never overlap: their sum is at most the busy time
    # (which also counts a loop's own time between its ops)
    assert 0 < sum(by.values()) <= report.busy_s(train_tr) + 1e-12


def test_quant_share_on_recorded_trace(train_tr):
    v = _reader("yi-9b-train.seq2k", "quant_share.train")({"trace": train_tr})
    by = xplane_meta.device_s_by_scope(train_tr)
    want = sum(by.get(s, 0.0) for s in xplane_meta.QUANTIZER_SCOPES)
    assert v == pytest.approx(100 * want / report.busy_s(train_tr))
    assert 0 < v < 100


def _hand_built():
    return {"window_s": 1.0, "chips": 1,
            "device_ops": [["%fusion.1 = f32[2] fusion()", 0.0, 0.2, 0],
                           ["%fusion.2 = f32[2] fusion()", 0.3, 0.1, 0],
                           ["%while.3 = (f32[2]) while()", 0.0, 0.15, 0],
                           ["%dot.4 = f32[2] dot()", 0.5, 0.3, 0]],
            "host_spans": [],
            "program_spans": [["serve.tick", -0.1, 0.3, 1],    # starts early
                              ["serve.tick", 0.2, 0.4, 1],
                              ["serve.sync", 0.25, 0.1, 1],
                              ["serve.sync", 0.4, 0.15, 1],
                              ["serve.sync", 0.3, 0.05, 2],    # other thread
                              ["serve.tick", 0.7, 0.2, 1]],
            "op_scopes": {
                "%fusion.1 = f32[2] fusion()": "jit(s)/jvp()/quant.fwd/mul",
                "%fusion.2 = f32[2] fusion()":
                    "jit(s)/transpose(jvp())/quant.bwd/rht/dot_general",
                "%while.3 = (f32[2]) while()": "jit(s)/quant.fwd/while",
                "%dot.4 = f32[2] dot()": "jit(s)/jvp()/gemm/dot_general"},
            "op_scope_clashes": 0}


def test_readers_on_hand_built_trace():
    tr = _hand_built()
    # ticks wholly inside the window: 0.2 + 0.4 (0.25 s of syncs) and 0.7
    assert xplane_meta.tick_host_s(tr) == pytest.approx([0.15, 0.2])
    assert _reader("granite-8b-bf16.chat", "tick_host_ms")(
        {"window": {"trace": tr}}) == pytest.approx(175.0)
    # quantizer ops 0.2 + 0.1 s of 0.6 busy; the while (a container) not
    assert _reader("yi-9b-train.seq2k", "quant_share.train")(
        {"trace": tr}) == pytest.approx(50.0)
    assert xplane_meta.device_s_by_scope(tr) == pytest.approx(
        {"quant.fwd": 0.2, "rht": 0.1, "gemm.fwd": 0.3})


def test_idle_by_span_splits_gaps_at_span_edges():
    tr = _hand_built()
    # idle 0.2-0.3, 0.4-0.5 and 0.8-1.0 on chip 0; the engine is thread 1
    by = xplane_meta.idle_by_span(tr)
    assert by == pytest.approx({"serve.sync": 0.15, "serve.tick": 0.15,
                                "outside any span": 0.1})
    tr["program_spans"] = tr["program_spans"][:-1]
    assert xplane_meta.idle_by_span(tr) == pytest.approx(
        {"serve.sync": 0.15, "serve.tick": 0.05, "outside any span": 0.2})


def test_with_meta_finds_the_newest_trace(tmp_path, monkeypatch):
    tr, full = _full(OLD)
    monkeypatch.setattr(xplane_meta, "OUT", tmp_path)
    assert xplane_meta.with_meta(tr) is None        # no trace file
    assert xplane_meta.with_meta(None) is None
    dest = tmp_path / "cell" / "trace" / "plugins" / "x.xplane.pb"
    dest.parent.mkdir(parents=True)
    shutil.copy(OLD, dest)
    assert xplane_meta.with_meta(tr) == full
    assert xplane_meta.with_meta(full) is full      # already extended
    other = dict(tr, window_s=tr["window_s"] + 0.5)  # another run's window
    assert xplane_meta.with_meta(other) is None
