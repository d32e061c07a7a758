"""Observability layer tests (obs/metrics, obs/tracing, engine wiring).

Acceptance (ISSUE 6):
  (a) registry semantics — label sets, idempotent/conflicting declaration,
      atomic snapshot, histogram bucket math + quantiles, Prometheus text;
  (b) per-request traces span queued -> prefill -> decode -> retired with
      monotonic timestamps, including rejection / cancellation paths;
  (c) the registry-backed stats view and the legacy dict agree exactly
      (cross-checked per key, including the spec-decode engine);
  (d) disabled mode is the NULL sentinel: plain-dict stats, no trace or
      metric objects, and greedy token streams BITWISE identical to the
      instrumented engine (paged and mesh-sharded);
  (e) the quantization-health probe reports finite per-site values with
      the paper's Table-1 ordering (MS-EDEN < plain SR relative MSE);
  (f) serve-layer hygiene: no print()/logging calls in src/repro/serve
      (all reporting flows through the obs hook);
  (g) host spans: the engine's `serve.*` TraceAnnotations appear in a
      profiler trace, nested tick > prefill/decode > sync, streams stay
      bitwise equal while they record, and a disabled engine makes none;
      the quartet2 train step carries its named scopes in HLO metadata.
"""

import ast
import json
import math
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import registry as arch_registry
from repro.models import lm
from repro.obs import (NULL, STAT_FLOAT_KEYS, STAT_KEYS, Instrumentation,
                       MetricsRegistry, RequestTrace, TraceSink,
                       legacy_stats_dict)
from repro.obs import instrumentation, tracing
from repro.serve.engine import (EngineConfig, QueueFull, Request, ServeEngine)

pytestmark = pytest.mark.obs


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

class TestRegistry:
    def test_counter_and_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("c", "a counter")
        c.inc()
        c.inc(2.5)
        assert c.get() == 3.5
        g = reg.gauge("g")
        g.set(7)
        g.inc(-3)
        assert g.get() == 4.0

    def test_label_series(self):
        reg = MetricsRegistry()
        c = reg.counter("req", labels=("route", "code"))
        c.labels("a", "200").inc()
        c.labels(route="a", code="200").inc()  # same series, by name
        c.labels("a", "500").inc()
        assert reg.value("req", route="a", code="200") == 2.0
        assert reg.value("req", route="a", code="500") == 1.0
        assert reg.value("req", route="b", code="200") == 0.0  # untouched

    def test_label_errors(self):
        reg = MetricsRegistry()
        c = reg.counter("c", labels=("x",))
        with pytest.raises(ValueError):
            c.labels()                       # missing value
        with pytest.raises(ValueError):
            c.labels("a", "b")               # too many
        with pytest.raises(ValueError):
            c.labels(y="a")                  # unknown name
        with pytest.raises(ValueError):
            c.inc()                          # labelled metric used bare

    def test_declare_idempotent_and_conflicting(self):
        reg = MetricsRegistry()
        a = reg.counter("m", labels=("x",))
        assert reg.counter("m", labels=("x",)) is a   # idempotent
        with pytest.raises(ValueError):
            reg.gauge("m", labels=("x",))             # kind conflict
        with pytest.raises(ValueError):
            reg.counter("m", labels=("y",))           # label conflict

    def test_histogram_bucket_math(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        child = h.labels()
        assert child.count == 4
        assert child.sum == 105.0
        assert child.counts == [1, 1, 1, 1]           # per-bucket
        assert child.cumulative() == [1, 2, 3, 4]     # prometheus-style
        assert child.buckets[-1] == math.inf          # +Inf auto-appended

    def test_histogram_quantile(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        assert math.isnan(h.quantile(0.5))            # empty
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        # rank 2 lands exactly at the top of the (1, 2] bucket
        assert h.quantile(0.5) == 2.0
        assert 0.0 < h.quantile(0.1) <= 1.0
        # q in the +Inf bucket degrades to the last finite bound
        assert h.quantile(1.0) == 4.0

    def test_snapshot_shape_and_atomicity(self):
        reg = MetricsRegistry()
        reg.counter("c", "help!", labels=("k",)).labels(k="v").inc(3)
        reg.histogram("h", buckets=(1.0,)).observe(0.5)
        snap = reg.snapshot()
        assert snap["c"]["type"] == "counter"
        assert snap["c"]["series"] == [{"labels": {"k": "v"}, "value": 3.0}]
        hs = snap["h"]["series"][0]
        assert hs["count"] == 1 and hs["sum"] == 0.5
        assert hs["buckets"][0] == (1.0, 1)
        # snapshot is a copy: later updates don't mutate it
        reg.counter("c", labels=("k",)).labels(k="v").inc()
        assert snap["c"]["series"][0]["value"] == 3.0

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "hits", labels=("k",)).labels(k="v").inc(2)
        reg.histogram("lat", buckets=(1.0,)).observe(0.5)
        text = reg.to_prometheus()
        assert "# TYPE c_total counter" in text
        assert 'c_total{k="v"} 2' in text
        assert "# TYPE lat histogram" in text
        assert 'lat_bucket{le="1.0"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_sum 0.5" in text
        assert "lat_count 1" in text
        json.loads(reg.to_json())  # valid JSON exposition too

    def test_child_registry_const_labels(self):
        reg = MetricsRegistry()
        child = reg.child(engine="7")
        child.counter("ticks_total").inc(4)
        child.histogram("step_s", labels=("phase",),
                        buckets=(1.0,)).labels(phase="synced").observe(0.1)
        assert reg.value("ticks_total", engine="7") == 4.0
        snap = reg.snapshot()
        assert snap["step_s"]["series"][0]["labels"] == {
            "engine": "7", "phase": "synced"}


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------

class TestTracing:
    def _retired_trace(self, req_id=0):
        tr = RequestTrace(req_id)
        tr.begin(tracing.QUEUED, 1.0)
        tr.end(tracing.QUEUED, 2.0)
        tr.begin(tracing.PREFILL, 2.0)
        tr.end(tracing.PREFILL, 5.0)
        tr.begin(tracing.DECODE, 5.0)
        tr.end(tracing.DECODE, 9.0, tokens=5)
        tr.finish(tracing.RETIRED, 9.0)
        return tr

    def test_span_ordering_and_latencies(self):
        tr = self._retired_trace()
        names = [s.name for s in tr.spans]
        assert names == ["queued", "prefill", "decode", "retired"]
        for s in tr.spans:
            assert s.t1 is not None and s.t1 >= s.t0
        t0s = [s.t0 for s in tr.spans]
        assert t0s == sorted(t0s)                       # monotonic
        assert tr.queue_wait_s == 1.0
        assert tr.ttft_s == 4.0                         # submit -> 1st token
        assert tr.decode_tok_s(5) == 1.0                # 4s / (5 - 1) tokens
        assert tr.state == tracing.RETIRED

    def test_finish_closes_open_spans(self):
        tr = RequestTrace(1)
        tr.begin(tracing.QUEUED, 0.0)
        tr.end(tracing.QUEUED, 1.0)
        tr.begin(tracing.PREFILL, 1.0)
        tr.finish(tracing.CANCELLED, 3.0)               # prefill still open
        assert tr.span(tracing.PREFILL).t1 == 3.0
        assert tr.spans[-1].name == tracing.CANCELLED
        assert tr.state == tracing.CANCELLED

    def test_sink_bounded_with_drop_count(self):
        sink = TraceSink(max_traces=2)
        for i in range(5):
            sink.append(self._retired_trace(i))
        assert len(sink.traces) == 2
        assert sink.dropped == 3
        assert [t.req_id for t in sink.traces] == [3, 4]  # oldest dropped
        assert sink.aggregates()["dropped"] == 3

    def test_aggregates_over_retired_only(self):
        sink = TraceSink()
        sink.append(self._retired_trace(0))
        rej = RequestTrace(1)
        rej.finish(tracing.REJECTED, 0.0)
        sink.append(rej)
        agg = sink.aggregates()
        assert agg["retired"] == 1 and agg["total"] == 2
        assert agg["ttft_s"]["count"] == 1
        assert agg["ttft_s"]["p50"] == 4.0
        assert agg["queue_wait_s"]["mean"] == 1.0

    def test_write_jsonl(self, tmp_path):
        sink = TraceSink()
        sink.append(self._retired_trace(0))
        path = tmp_path / "trace.jsonl"
        n = sink.write_jsonl(str(path))
        events = [json.loads(line) for line in path.read_text().splitlines()]
        assert n == len(events) == 4
        assert [e["span"] for e in events] == [
            "queued", "prefill", "decode", "retired"]
        assert all(e["state"] == "retired" for e in events)
        assert all(e["t1"] >= e["t0"] for e in events)


# --------------------------------------------------------------------------
# engine wiring
# --------------------------------------------------------------------------

def _cfg():
    return arch_registry.get("llama_200m").reduced()


def _params(cfg):
    return lm.init(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, lens=(9, 13)):
    rng = np.random.RandomState(1)
    return [list(map(int, rng.randint(0, cfg.vocab, n))) for n in lens]


def _engine(cfg, params, obs=None, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("scheme", "bf16")
    kw.setdefault("prequant", False)
    return ServeEngine(cfg, params, EngineConfig(obs=obs, **kw))


class TestEngineInstrumentation:
    def test_lifecycle_counters_traces_and_result_latencies(self):
        cfg = _cfg()
        eng = _engine(cfg, _params(cfg),
                      obs=Instrumentation(registry=MetricsRegistry()))
        prompts = _prompts(cfg)
        ids = [eng.submit(Request(prompt=p, max_new=3)) for p in prompts]
        results = {r.req_id: r for r in eng.run()}
        obs, reg = eng.obs, eng.obs.registry

        # (c) registry counters == legacy stats surface, key for key
        for k in STAT_KEYS:
            name = (f"serve_engine_{k[:-2]}_seconds_total"
                    if k in STAT_FLOAT_KEYS else f"serve_engine_{k}_total")
            assert reg.value(name, engine=obs.engine_label) == pytest.approx(
                eng.stats[k]), k
        assert eng.stats["finished"] == len(prompts)

        # per-request latencies surfaced on the results
        for i in ids:
            r = results[i]
            assert r.queue_wait_s is not None and r.queue_wait_s >= 0
            assert r.ttft_s is not None and r.ttft_s >= r.queue_wait_s
            assert r.decode_tok_s is not None and r.decode_tok_s > 0

        # (b) every retired trace runs the full span ladder, monotonic
        assert len(obs.trace_sink.traces) == len(prompts)
        for tr in obs.trace_sink.traces:
            assert tr.state == tracing.RETIRED
            names = [s.name for s in tr.spans]
            assert names == ["queued", "prefill", "decode", "retired"]
            ts = [t for s in tr.spans for t in (s.t0, s.t1)]
            assert ts == sorted(ts)
        agg = obs.trace_sink.aggregates()
        assert agg["retired"] == len(prompts)
        assert agg["ttft_s"]["count"] == len(prompts)

        # prometheus exposition carries the telemetry families
        text = obs.prometheus()
        for family in ("serve_queue_depth", "serve_slots",
                       "serve_pool_free_blocks",
                       "serve_pool_fragmentation_ratio",
                       "serve_request_ttft_seconds_bucket",
                       "serve_decode_step_seconds_bucket",
                       "serve_engine_decode_tokens_total"):
            assert family in text, family
        # all slots free again at the final tick
        assert reg.value("serve_slots", engine=obs.engine_label,
                         state="free") == eng.econf.n_slots
        # step histograms saw both phases; synced >= dispatch (the cache
        # sync is included in synced only)
        dec = reg.get("serve_decode_step_seconds")
        disp = dec.labels(engine=obs.engine_label, phase="dispatch")
        sync = dec.labels(engine=obs.engine_label, phase="synced")
        assert disp.count == sync.count == eng.stats["decode_steps"]
        assert sync.sum >= disp.sum

    def test_rejection_traces(self):
        cfg = _cfg()
        eng = _engine(cfg, _params(cfg), max_queue=1,
                      obs=Instrumentation(registry=MetricsRegistry()))
        eng.submit(Request(prompt=[1, 2, 3], max_new=2))
        with pytest.raises(QueueFull):
            eng.submit(Request(prompt=[4, 5, 6], max_new=2))
        with pytest.raises(ValueError):  # unservable: exceeds pool capacity
            eng.queue.clear()
            eng.submit(Request(prompt=list(range(500)), max_new=2))
        reasons = [tr.spans[-1].attrs.get("reason")
                   for tr in eng.obs.trace_sink.traces
                   if tr.state == tracing.REJECTED]
        assert reasons == ["queue_full", "unservable"]
        assert eng.stats["rejected"] == 2

    def test_cancel_queued_and_inflight(self):
        cfg = _cfg()
        eng = _engine(cfg, _params(cfg), n_slots=1,
                      obs=Instrumentation(registry=MetricsRegistry()))
        free0 = eng.pool.free_block_count
        p1, p2 = _prompts(cfg)
        i1 = eng.submit(Request(prompt=p1, max_new=4))
        i2 = eng.submit(Request(prompt=p2, max_new=4))
        eng.step()                       # admits i1, leaves i2 queued
        assert eng.cancel(i2) is True    # queued-path cancel
        eng.step()
        assert eng.cancel(i1) is True    # in-flight cancel frees the slot
        assert eng.cancel(i1) is False   # unknown now
        assert not eng.has_work()
        assert eng.pool.free_block_count == free0   # blocks conserved
        assert eng.stats["cancelled"] == 2
        states = sorted(tr.state for tr in eng.obs.trace_sink.traces)
        assert states == ["cancelled", "cancelled"]

    def test_stats_view_is_dict_compatible(self):
        cfg = _cfg()
        eng = _engine(cfg, _params(cfg),
                      obs=Instrumentation(registry=MetricsRegistry()))
        # same key set + iteration order as the legacy dict
        assert list(eng.stats) == list(legacy_stats_dict())
        eng.submit(Request(prompt=[1, 2, 3], max_new=2))
        eng.run()
        assert isinstance(eng.stats["decode_tokens"], int)
        assert isinstance(eng.stats["decode_s"], float)
        # the bench reset idiom writes through to the registry
        for k in eng.stats:
            eng.stats[k] = 0 if isinstance(eng.stats[k], int) else 0.0
        assert dict(eng.stats) == legacy_stats_dict()
        assert eng.obs.registry.value(
            "serve_engine_decode_tokens_total",
            engine=eng.obs.engine_label) == 0.0
        with pytest.raises(TypeError):
            del eng.stats["ticks"]       # fixed key set

    def test_spec_engine_acceptance_histogram(self):
        cfg = _cfg()
        obs = Instrumentation(registry=MetricsRegistry())
        eng = _engine(cfg, _params(cfg), spec_k=2, draft_layers=1, obs=obs)
        for p in _prompts(cfg):
            eng.submit(Request(prompt=p, max_new=6))
        eng.run()
        assert eng.stats["spec_rounds"] > 0
        hist = obs.registry.get("serve_spec_accepted_per_round")
        child = hist.labels(engine=obs.engine_label)
        assert child.count == eng.stats["spec_rounds"]
        assert child.sum == eng.stats["accepted_tokens"]

    def test_prefix_cache_counters(self):
        cfg = _cfg()
        obs = Instrumentation(registry=MetricsRegistry())
        eng = _engine(cfg, _params(cfg), prefix_cache=True, obs=obs)
        shared = _prompts(cfg, lens=(24,))[0]
        eng.submit(Request(prompt=list(shared), max_new=2))
        eng.run()                                     # primes the cache
        eng.submit(Request(prompt=shared + [5, 6, 7], max_new=2))
        eng.run()                                     # aliases the prefix
        label = obs.engine_label
        assert obs.registry.value("serve_prefix_cache_hits_total",
                                  engine=label) == eng.stats["prefix_hits"]
        assert obs.registry.value(
            "serve_prefix_cache_hit_tokens_total",
            engine=label) == eng.stats["prefill_skipped_tokens"] > 0
        assert obs.registry.value("serve_pool_blocks_allocated_total",
                                  engine=label) > 0


# --------------------------------------------------------------------------
# disabled mode + determinism
# --------------------------------------------------------------------------

def _tokens(cfg, params, prompts, obs=None, **kw):
    eng = _engine(cfg, params, obs=obs, **kw)
    ids = [eng.submit(Request(prompt=p, max_new=4)) for p in prompts]
    res = {r.req_id: r for r in eng.run()}
    return [res[i].tokens for i in ids], [res[i] for i in ids]


class TestDisabledAndDeterminism:
    def test_disabled_mode_is_null_sentinel(self, monkeypatch):
        made = []

        def counting(*a, **k):
            made.append(a)
            return instrumentation.NO_SPAN

        monkeypatch.setattr(instrumentation, "TraceAnnotation", counting)
        cfg = _cfg()
        eng = _engine(cfg, _params(cfg))     # obs=None
        assert eng.obs is NULL
        assert eng._span is instrumentation.no_span
        assert NULL.enabled is False
        assert type(eng.stats) is dict       # plain legacy dict, no view
        # the sentinel carries NOTHING: any accidental hook use fails loudly
        with pytest.raises(AttributeError):
            NULL.on_submit
        with pytest.raises(AttributeError):
            NULL.extra = 1                   # slotted: no attr creation
        _, results = _tokens(cfg, _params(cfg), _prompts(cfg))
        assert all(r.ttft_s is None and r.queue_wait_s is None
                   for r in results)
        assert made == []                    # no span object was made
        _tokens(cfg, _params(cfg), _prompts(cfg),
                obs=Instrumentation(registry=MetricsRegistry()))
        assert made                          # control: enabled spans are

    def test_streams_bitwise_unchanged_paged(self):
        cfg, params = _cfg(), None
        params = _params(cfg)
        prompts = _prompts(cfg)
        plain, _ = _tokens(cfg, params, prompts)
        traced, _ = _tokens(cfg, params, prompts,
                            obs=Instrumentation(registry=MetricsRegistry()))
        assert plain == traced

    @pytest.mark.skipif(jax.device_count() < 2,
                        reason="simulated mesh needs >= 2 host devices")
    def test_streams_bitwise_unchanged_sharded(self):
        from repro.launch.mesh import make_serve_mesh
        cfg = _cfg()
        params = _params(cfg)
        prompts = _prompts(cfg)
        mesh = make_serve_mesh(2, 1)
        plain, _ = _tokens(cfg, params, prompts, mesh=mesh)
        traced, _ = _tokens(cfg, params, prompts, mesh=mesh,
                            obs=Instrumentation(registry=MetricsRegistry()))
        assert plain == traced


# --------------------------------------------------------------------------
# host spans on the profiler's clock, named scopes in the train step
# --------------------------------------------------------------------------

TICK_SPANS = {"serve.tick", "serve.admit", "serve.prefill", "serve.decode",
              "serve.inputs", "serve.tables", "serve.dispatch",
              "serve.sample", "serve.sync", "serve.readback", "serve.flush",
              "serve.retire", "serve.gauges"}


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """A tiny engine run under `jax.profiler.trace`: the `serve.*` events
    of each host line as (name, start, end), and the streams."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg)
    eng = _engine(cfg, params, obs=Instrumentation(registry=MetricsRegistry()))
    eng.token_hook = lambda req, new, result: None
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        ids = [eng.submit(Request(prompt=p, max_new=4)) for p in prompts]
        res = {r.req_id: r for r in eng.run()}
    path = next(out.rglob("*.xplane.pb"))
    lines = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("serve.")]
            if evs:
                lines.append(evs)
    return lines, [res[i].tokens for i in ids], (cfg, params, prompts)


class TestHostSpans:
    def test_spans_named_and_nested(self, traced_engine):
        lines, _, _ = traced_engine
        assert len(lines) == 1               # all on the engine's thread
        evs = lines[0]
        assert {n for n, _, _ in evs} == TICK_SPANS

        def inside(ev, names):
            return any(n in names and s <= ev[1] and ev[2] <= e
                       for n, s, e in evs)

        syncs = [ev for ev in evs if ev[0] == "serve.sync"]
        assert syncs
        assert all(inside(ev, {"serve.prefill", "serve.decode"})
                   for ev in syncs)
        assert all(inside(ev, {"serve.tick"}) for ev in evs
                   if ev[0] in ("serve.prefill", "serve.decode"))

    def test_streams_bitwise_equal_while_spans_record(self, traced_engine):
        _, traced, (cfg, params, prompts) = traced_engine
        plain, _ = _tokens(cfg, params, prompts)
        assert traced == plain

    def test_quartet2_train_step_hlo_carries_scopes(self, monkeypatch):
        import contextlib
        import re

        from repro.core import scopes as SC
        from repro.train.train_step import make_train_step
        cfg = _cfg()
        tok = jax.ShapeDtypeStruct((2, 64), np.int32)

        def lowered():
            init, step = make_train_step(cfg, "quartet2")  # a fresh trace
            state = jax.eval_shape(init, jax.eval_shape(
                lambda: lm.init(cfg, jax.random.PRNGKey(0))))
            return jax.jit(step).lower(state, {"tokens": tok, "labels": tok})

        low = lowered()
        hlo = low.as_text(dialect="hlo", debug_info=True)
        comps = {c for name in re.findall(r'op_name="([^"]*)"', hlo)
                 for c in name.split("/")}
        assert {SC.QUANT_FWD, SC.QUANT_BWD, SC.RHT, SC.GEMM,
                SC.OPTIMIZER} <= comps
        # scopes are metadata only: without them the module is the same
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        assert lowered().as_text() == low.as_text()


# --------------------------------------------------------------------------
# quantization-health probe
# --------------------------------------------------------------------------

class TestQuantProbe:
    def test_probe_values_and_table1_ordering(self):
        from repro.obs.quant_probe import QuantProbe
        cfg = _cfg()
        params = _params(cfg)
        reg = MetricsRegistry()
        probe = QuantProbe(scheme="quartet2", max_sites=2, registry=reg)
        out = probe.probe_params(params, phase="prequant")
        assert out
        for site, vals in out.items():
            assert all(math.isfinite(v) for v in vals.values())
            # paper Table 1 on real weights: MS-EDEN beats plain SR
            assert vals["ms_eden_mse_rel"] < vals["sr_mse_rel"]
            assert 0.0 <= vals["fwd_scale_sat_frac"] <= 1.0
            assert 0.0 <= vals["rht_outlier_mass"] < 0.5
            assert reg.value("nvfp4_quant_mse_rel", site=site,
                             phase="prequant", quantizer="ms_eden"
                             ) == pytest.approx(vals["ms_eden_mse_rel"])
        assert reg.value("nvfp4_probe_samples_total",
                         phase="prequant") == len(out)

    def test_probe_deterministic(self):
        from repro.obs.quant_probe import QuantProbe
        cfg = _cfg()
        params = _params(cfg)
        a = QuantProbe(max_sites=2, registry=MetricsRegistry())
        b = QuantProbe(max_sites=2, registry=MetricsRegistry())
        assert a.probe_params(params, step=5) == b.probe_params(params, step=5)

    def test_should_sample_schedule(self):
        from repro.obs.quant_probe import QuantProbe
        probe = QuantProbe(registry=MetricsRegistry())
        assert not any(probe.should_sample(s) for s in range(10))  # off
        probe.every_n = 5
        assert [s for s in range(11) if probe.should_sample(s)] == [0, 5, 10]


# --------------------------------------------------------------------------
# serve-layer hygiene (satellite: everything reports through obs)
# --------------------------------------------------------------------------

def test_no_print_or_logging_in_serve_layer():
    serve_dir = (pathlib.Path(__file__).resolve().parent.parent
                 / "src" / "repro" / "serve")
    offenders = []
    for path in sorted(serve_dir.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id == "print":
                offenders.append(f"{path.name}:{node.lineno} print()")
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "logging"):
                offenders.append(f"{path.name}:{node.lineno} logging call")
    assert not offenders, offenders
