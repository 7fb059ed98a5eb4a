"""Tests for the ``repro.obs`` observability subsystem.

Covers: span nesting and the disabled no-op path, error-attributed spans
from failing flow steps, Chrome trace_event export (golden file +
schema validation), the Prometheus text exposition (golden file),
registry semantics, ``export_bench`` round-trips, the range-analysis
cache counters across mutate-then-reanalyze, range provenance
(``SiraModel.explain``), the ServingMetrics facade equivalence, the
folding-search telemetry, and the tier-1 tracing smoke (traced flow +
compile validates against the Chrome schema).
"""
import json
import pathlib

import pytest

from repro.core import SiraModel, analyze, build_flow
from repro.core.workloads import make_cnv, make_tfc
from repro.obs import (NULL_SPAN, MetricsRegistry, ProvenanceChain,
                       RangeProvenance, Tracer, build_chain,
                       disable_tracing, enable_tracing, export_bench,
                       get_tracer, validate_chrome_trace)

GOLDEN = pathlib.Path(__file__).parent / "golden"


class FakeClock:
    """Deterministic monotonic clock: each call advances 1 ms."""

    def __init__(self, t0: float = 100.0, step: float = 0.001):
        self.t = t0
        self.step = step

    def __call__(self) -> float:
        t = self.t
        self.t += self.step
        return t


@pytest.fixture
def global_tracer():
    """Install a fresh enabled global tracer; restore the no-op one."""
    tracer = enable_tracing()
    yield tracer
    disable_tracing()


# --------------------------------------------------------------------------
# tracer: spans, nesting, disabled path
# --------------------------------------------------------------------------

def test_span_nesting_depth_and_order():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    # completion order: children before parents
    assert [s.name for s in tr.spans] == ["inner", "inner2", "outer"]
    by_name = {s.name: s for s in tr.spans}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["inner2"].depth == 1
    assert by_name["outer"].attrs == {"kind": "test"}
    # children start after and end before the parent
    o, i = by_name["outer"], by_name["inner"]
    assert o.ts_us <= i.ts_us
    assert i.ts_us + i.dur_us <= o.ts_us + o.dur_us


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.span("anything", x=1)
    assert sp is NULL_SPAN                 # shared singleton, no alloc
    with sp:
        sp.set_attr("y", 2)
    tr.count("c", 5)
    assert tr.spans == []
    assert tr.counters == {}


def test_default_global_tracer_disabled():
    # the restored global must be the no-op tracer — instrumented
    # library code pays one flag check unless enable_tracing() ran
    disable_tracing()
    assert not get_tracer().enabled
    assert get_tracer().span("x") is NULL_SPAN


def test_span_error_attr_on_exception():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError, match="kaboom"):
        with tr.span("will_fail", stage=3):
            raise RuntimeError("kaboom")
    (s,) = tr.spans
    assert s.name == "will_fail"
    assert s.attrs["error"] == "RuntimeError: kaboom"
    assert s.attrs["stage"] == 3
    assert s.dur_us >= 0


class _Note:
    """A profiler annotation that logs its life into ``log``."""

    def __init__(self, log, name, **attrs):
        self.log, self.name = log, name
        log.append(("enter", name, dict(attrs)))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, None))

    def set_metadata(self, **attrs):
        self.log.append(("meta", self.name, attrs))


def test_profiler_sink_gets_every_span_and_keeps_none():
    log = []
    tr = Tracer(clock=FakeClock(),
                annotate=lambda name, **a: _Note(log, name, **a))
    with tr.span("outer", slot=1) as sp:
        with tr.span("inner"):
            pass
        sp.set_attr("chunks", 3)
    with pytest.raises(RuntimeError):
        with tr.span("fails"):
            raise RuntimeError("kaboom")
    assert log == [("enter", "outer", {"slot": 1}),
                   ("enter", "inner", {}), ("exit", "inner", None),
                   ("meta", "outer", {"chunks": 3}),
                   ("exit", "outer", None),
                   ("enter", "fails", {}),
                   ("meta", "fails", {"error": "RuntimeError: kaboom"}),
                   ("exit", "fails", None)]
    assert tr.spans == []                  # the sink holds them, not us
    assert sp.dur_s is not None


def test_enable_tracing_installs_the_sink():
    def sink(name, **attrs):
        return NULL_SPAN
    try:
        assert enable_tracing(annotate=sink).annotate is sink
        assert get_tracer().annotate is sink
        assert enable_tracing().annotate is None
    finally:
        disable_tracing()


def test_counters_accumulate():
    tr = Tracer(clock=FakeClock())
    tr.count("hits")
    tr.count("hits", 2, where="x")
    tr.count("misses", 0.5)
    assert tr.counters == {"hits": 3.0, "misses": 0.5}


# --------------------------------------------------------------------------
# Chrome trace_event export
# --------------------------------------------------------------------------

def _normalized_chrome(payload):
    """pid/tid vary per process/thread — zero them for golden compare."""
    out = json.loads(json.dumps(payload))
    for ev in out["traceEvents"]:
        ev["pid"] = 0
        ev["tid"] = 0
    return out


def test_chrome_trace_golden():
    tr = Tracer(clock=FakeClock())
    with tr.span("flow:build", model="tfc", steps=2):
        with tr.span("step:streamline"):
            tr.count("range_cache.miss", attrs_ignored=1)
        with tr.span("step:minimize", modified=True):
            pass
    payload = tr.to_chrome_json()
    validate_chrome_trace(payload)
    got = _normalized_chrome(payload)
    golden_path = GOLDEN / "trace_chrome.json"
    want = json.loads(golden_path.read_text())
    assert got == want, (
        f"Chrome trace drifted from golden {golden_path} — if the change "
        f"is deliberate, regenerate the golden from the normalized "
        f"payload")


def test_chrome_trace_timestamps_anchor_at_outer_span():
    # the epoch must anchor at the *earliest* sample: an inner count()
    # before any span completes must not push the outer span negative
    tr = Tracer(clock=FakeClock())
    with tr.span("outer"):
        tr.count("c")
    payload = tr.to_chrome_json()
    validate_chrome_trace(payload)          # rejects negative ts
    assert all(ev["ts"] >= 0 for ev in payload["traceEvents"])


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace([])           # not an object
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x"}]})
    bad_phase = {"traceEvents": [dict(name="x", ph="Z", ts=0.0,
                                      pid=1, tid=1)]}
    with pytest.raises(ValueError, match="phase"):
        validate_chrome_trace(bad_phase)
    neg = {"traceEvents": [dict(name="x", ph="X", ts=-1.0, dur=1.0,
                                pid=1, tid=1)]}
    with pytest.raises(ValueError, match="negative"):
        validate_chrome_trace(neg)


def test_write_chrome_trace_roundtrip(tmp_path):
    tr = Tracer(clock=FakeClock())
    with tr.span("a"):
        pass
    path = tmp_path / "out.json"
    tr.write_chrome_trace(str(path))
    payload = json.loads(path.read_text())
    validate_chrome_trace(payload)
    assert any(ev["name"] == "a" for ev in payload["traceEvents"])


# --------------------------------------------------------------------------
# metrics registry + Prometheus exposition
# --------------------------------------------------------------------------

def _sample_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests served",
                    labels=("engine",))
    c.labels(engine="paged").inc(3)
    c.labels(engine="static").inc()
    reg.gauge("slots", "configured batch slots").set(4)
    h = reg.histogram("ttft_seconds", "time to first token",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    return reg


def test_prometheus_golden():
    got = _sample_registry().to_prometheus()
    golden_path = GOLDEN / "metrics.prom"
    want = golden_path.read_text()
    assert got == want, (
        f"Prometheus exposition drifted from golden {golden_path}")


def test_prometheus_histogram_shape():
    text = _sample_registry().to_prometheus()
    assert 'ttft_seconds_bucket{le="0.01"} 1' in text
    assert 'ttft_seconds_bucket{le="+Inf"} 4' in text
    assert "ttft_seconds_count 4" in text
    assert "# TYPE requests_total counter" in text
    assert 'requests_total{engine="paged"} 3' in text


def test_registry_idempotent_reregistration():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "first", labels=("k",))
    b = reg.counter("x_total", "ignored on re-register", labels=("k",))
    assert a is b
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")                # kind mismatch
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labels=("other",))  # label mismatch


def test_metric_label_discipline():
    reg = MetricsRegistry()
    c = reg.counter("y_total", labels=("a",))
    with pytest.raises(ValueError, match="takes labels"):
        c.labels(b="1")
    with pytest.raises(ValueError, match="call .labels"):
        c.inc()                             # labeled metric, bare inc
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("h", buckets=())


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="only go up"):
        reg.counter("n_total").inc(-1)
    g = reg.gauge("g")
    g.dec(2)
    assert g.value == -2


def test_registry_json_export():
    j = _sample_registry().to_json()
    assert j["slots"]["type"] == "gauge"
    assert j["slots"]["samples"][0]["value"] == 4.0
    hist = j["ttft_seconds"]["samples"][0]
    assert hist["count"] == 4 and hist["inf"] == 1


def test_export_bench_roundtrip(tmp_path):
    payload = dict(backend="cpu", results=[
        dict(workload="TFC", speedup=2.5, nodes=10, ok=True, tag="x"),
        dict(workload="CNV", speedup=4.0, nodes=20, ok=False, tag="y"),
    ])
    out = tmp_path / "BENCH_backend.json"
    reg = export_bench(payload, str(out), key=("workload",))
    # JSON artifact unchanged (baselines keep working)
    assert json.loads(out.read_text()) == payload
    prom = (tmp_path / "BENCH_backend.prom").read_text()
    assert 'bench_backend_speedup{workload="TFC"} 2.5' in prom
    assert 'bench_backend_nodes{workload="CNV"} 20' in prom
    # bools and strings are not metrics
    assert "bench_backend_ok" not in prom
    assert "bench_backend_tag" not in prom
    g = reg.gauge("bench_backend_speedup", labels=("workload",))
    assert g.labels(workload="CNV").value == 4.0


# --------------------------------------------------------------------------
# analysis-cache counters (model layer)
# --------------------------------------------------------------------------

def test_range_cache_counters_across_mutation(global_tracer):
    model = SiraModel.from_workload(make_tfc())
    _ = model.ranges                        # cold: miss
    _ = model.ranges                        # cached: hit
    model.graph.touch()                     # version bump invalidates
    _ = model.ranges                        # recompute: miss
    c = global_tracer.counters
    assert c.get("range_cache.miss") == 2
    assert c.get("range_cache.hit") == 1
    model.invalidate()
    assert c.get("range_cache.invalidate") == 1


# --------------------------------------------------------------------------
# range provenance / explain()
# --------------------------------------------------------------------------

def test_explain_reaches_seed_on_cnv():
    model = build_flow(make_cnv()).model
    # pin the queried tensor node-positionally: the output of the first
    # accumulator node (tensor names differ between flow variants)
    rep = model.metadata["accumulator_reports"][0]
    node = next(n for n in model.graph.nodes if n.name == rep.node_name)
    tensor = node.outputs[0]
    chain = model.explain(tensor)
    assert isinstance(chain, ProvenanceChain)
    assert chain.tensor == tensor
    assert len(chain) >= 2
    first, last = chain.entries[0], chain.entries[-1]
    assert first.tensor == tensor
    assert first.node_name == rep.node_name
    assert first.culprit in first.in_widths
    assert last.culprit is None             # walked back to a graph seed
    assert last.handler in ("input", "const")
    text = chain.render()
    assert tensor in text and "widened by" in text
    # explain() must not have invalidated the analysis cache
    assert model.analysis_cached


def test_explain_unknown_tensor_raises():
    model = SiraModel.from_workload(make_tfc())
    with pytest.raises(KeyError, match="no provenance recorded"):
        model.explain("definitely_not_a_tensor")


def test_provenance_recorded_via_analyze():
    wl = make_tfc()
    model = SiraModel.from_workload(wl)
    record = {}
    analyze(model.graph, model.input_ranges, record=record)
    assert record                           # every tensor attributed
    for name, rec in record.items():
        assert isinstance(rec, RangeProvenance)
        assert rec.tensor == name
    inp = model.graph.inputs[0]
    assert record[inp].op_type == "input"
    chain = build_chain(model.graph.outputs[0], record)
    assert chain.entries[-1].culprit is None


# --------------------------------------------------------------------------
# flow + compile tracing (the tier-1 tracing smoke)
# --------------------------------------------------------------------------

def test_traced_flow_and_compile_smoke(global_tracer):
    model = build_flow(make_tfc()).model
    model.compile()
    payload = global_tracer.to_chrome_json()
    validate_chrome_trace(payload)
    by_name = {}
    for s in global_tracer.spans:
        by_name.setdefault(s.name, s)
    assert "flow:build" in by_name and by_name["flow:build"].depth == 0
    step_spans = [s for s in global_tracer.spans
                  if s.name.startswith("step:")]
    assert step_spans and all(s.depth == 1 for s in step_spans)
    prop = [s for s in global_tracer.spans
            if s.name == "analysis:propagate"]
    assert prop and all(s.depth >= 2 for s in prop)
    assert "compile:lower" in by_name
    assert "compile:build_plan" in by_name
    # StepReport timing survives the instrumentation
    assert global_tracer.counters.get("range_cache.miss", 0) >= 1


def test_failing_flow_step_closes_spans_with_error(global_tracer):
    def explode(model):
        raise RuntimeError("step boom")

    with pytest.raises(RuntimeError, match="step boom"):
        build_flow(make_tfc(), steps=["explicitize_quantizers", explode])
    names = [s.name for s in global_tracer.spans]
    assert "step:explode" in names
    failed = next(s for s in global_tracer.spans
                  if s.name == "step:explode")
    assert failed.attrs["error"] == "RuntimeError: step boom"
    assert "analysis_calls" in failed.attrs
    # the enclosing flow span also closed (children before parents)
    outer = next(s for s in global_tracer.spans
                 if s.name == "flow:build")
    assert outer.attrs["error"] == "RuntimeError: step boom"
    validate_chrome_trace(global_tracer.to_chrome_json())


# --------------------------------------------------------------------------
# folding-search telemetry
# --------------------------------------------------------------------------

def test_folding_search_telemetry(global_tracer):
    from repro.dataflow import DeviceBudget, search_folding

    model = build_flow(make_tfc()).model
    fold = search_folding(model, target_fps=1000.0, device="pynq-z1")
    assert fold.feasible
    c = global_tracer.counters
    assert c.get("folding.candidates", 0) >= 1
    spans = {s.name: s for s in global_tracer.spans}
    assert spans["dse:search_folding"].attrs["feasible"] is True

    tiny = DeviceBudget("tiny", luts=400, dsps=1, brams=1)
    search_folding(model, target_fps=1000.0, device=tiny)
    rejects = [k for k in global_tracer.counters
               if k.startswith("folding.reject.")]
    assert rejects, "infeasible search must record rejection counters"


# --------------------------------------------------------------------------
# ServingMetrics facade
# --------------------------------------------------------------------------

def test_serving_metrics_facade_equivalence():
    from repro.serve.metrics import ServingMetrics

    clock = FakeClock(t0=0.0, step=0.25)
    m = ServingMetrics(clock=clock)
    m.on_submit(0, prompt_tokens=5)
    m.on_prefill_chunk()
    m.on_prefill_chunk()
    for _ in range(4):
        m.on_decode_step(active_slots=1, total_slots=2, tokens=1)
        m.on_token(0)
    m.on_spec_step(proposed=4, accepted=2)
    m.on_finish(0)

    s = m.summary()
    assert s["requests"] == 1
    assert s["total_tokens"] == 4
    assert s["decode_steps"] == 4
    assert s["prefill_chunks"] == 2
    assert s["spec_proposed"] == 4 and s["spec_accepted"] == 2
    assert s["acceptance_rate"] == 0.5
    assert s["slot_occupancy"] == 0.5       # 4 active / 8 capacity

    # the facade's summary numbers and the Prometheus exposition come
    # from the same registry — scrape and cross-check
    text = m.to_prometheus()
    assert "serving_decode_steps_total 4" in text
    assert "serving_prefill_chunks_total 2" in text
    assert "serving_spec_accepted_total 2" in text
    assert "serving_tokens_total 4" in text
    assert "serving_ttft_seconds_count 1" in text
    # 3 inter-token gaps of one 0.25s clock tick each
    assert "serving_token_latency_seconds_count 3" in text
    assert "serving_token_latency_seconds_sum 0.75" in text
    assert s["mean_token_latency_s"] == pytest.approx(0.25)
    # count fields stay plain ints (historical API)
    assert isinstance(m.decode_steps, int)
    assert m.decode_steps == 4


def test_serving_metrics_fresh_registry_per_instance():
    from repro.serve.metrics import ServingMetrics

    a = ServingMetrics(clock=FakeClock())
    a.on_decode_step(1, 2, tokens=1)
    b = ServingMetrics(clock=FakeClock())   # reset_metrics() semantics
    assert b.decode_steps == 0
    assert a.decode_steps == 1
    assert a.registry is not b.registry


# --------------------------------------------------------------------------
# CompiledSiraModel.profile()
# --------------------------------------------------------------------------

def test_compiled_profile_spans_and_equivalence(global_tracer):
    import numpy as np

    model = build_flow(make_tfc()).model
    compiled = model.compile()
    feeds = next(model.sample_inputs())
    want = compiled(feeds)
    got = compiled.profile(feeds)
    assert set(want) == set(got)
    for k in want:
        np.testing.assert_allclose(np.asarray(want[k]),
                                   np.asarray(got[k]),
                                   rtol=1e-6, atol=1e-6)
    kernel_spans = [s for s in global_tracer.spans
                    if s.name.startswith("kernel:")]
    assert kernel_spans
    assert any(s.name == "compiled:profile"
               for s in global_tracer.spans)
