"""Device idle put down to the engine's spans: innermost attribution,
the window left to the harness's annotations, the gap labels, the
per-step readings, and a trace recorded on a TPU v5e with the sink on."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import spans as sp
from benchmarks.chip import trace_reduce as tr
from benchmarks.chip.spec import HERE, ROOT

MS = 1e6                                   # nanoseconds
TESTDATA = HERE / "testdata"


def ev(name, start_ms, dur_ms):
    return tr.Ev(name, start_ms * MS, dur_ms * MS)


def _synthetic():
    """Device busy 0-6, 10-15 and 40-50 of a 60 ms window."""
    ops = {"/device:TPU:0": [ev("fusion.1", 0, 4), ev("fusion.2", 2, 4),
                             ev("copy.3", 10, 5), ev("fusion.1", 40, 10)]}
    notes = [ev("harness:step", 0, 16), ev("harness:wait_arrival", 16, 22),
             ev("harness:step", 38, 14), ev("harness:drain", 52, 8)]
    spans = [ev("engine:step", 1, 14),
             ev("engine:prefill", 1, 7), ev("engine:prefill_sync", 6, 2),
             ev("engine:decode_dispatch", 8, 1),
             ev("engine:decode_sync", 9, 6),
             ev("engine:step", 39, 12), ev("engine:decode_dispatch", 39, 1),
             ev("engine:record", 50, 1)]
    return ops, notes, spans


def test_idle_goes_to_the_innermost_span_and_adds_up():
    ops, notes, spans = _synthetic()
    s = sp.reduce_events(ops, notes, spans)
    assert s.idle_by_span_s == pytest.approx({
        "engine:prefill_sync": 2e-3,      # 6-8
        "engine:decode_dispatch": 2e-3,   # 8-9, 39-40
        "engine:decode_sync": 1e-3,       # 9-10
        "harness:step": 3e-3,             # 15-16, 38-39, 51-52
        "harness:wait_arrival": 22e-3,    # 16-38
        "engine:record": 1e-3,            # 50-51
        "harness:drain": 8e-3})           # 52-60
    assert sum(s.idle_by_span_s.values()) == \
        pytest.approx(s.window_s - s.busy_s)


def test_engine_spans_leave_the_window_alone():
    """Spans outside the harness's annotations (set-up, the drain of an
    untraced stretch) move neither the window nor the busy time."""
    ops, notes, spans = _synthetic()
    outside = spans + [ev("engine:step", -30, 20), ev("engine:step", 65, 9)]
    s = sp.reduce_events(ops, notes, outside)
    base = tr.reduce_events(ops, {}, notes)
    assert s.window_s == pytest.approx(base.window_s)
    assert s.busy_s == pytest.approx(base.busy_s)
    assert s.idle_by_span_s == pytest.approx(
        sp.reduce_events(ops, notes, spans).idle_by_span_s)
    assert s.span_count["engine:step"] == 2      # those starting inside


def test_gap_labels_take_the_span_that_most_of_the_gap_went_to():
    ops, notes, spans = _synthetic()
    s = sp.reduce_events(ops, notes, spans)
    assert s.idle_gaps[:3] == [
        ("harness:wait_arrival", pytest.approx(25e-3)),      # 15-40
        ("harness:drain", pytest.approx(10e-3)),             # 50-60
        ("engine:prefill_sync", pytest.approx(4e-3))]        # 6-10


def test_time_under_no_span_goes_to_none():
    ops = {"/device:TPU:0": [ev("fusion.1", 4, 2)]}
    notes = [ev("harness:step", 0, 3), ev("harness:step", 8.5, 1.5)]
    s = sp.reduce_events(ops, notes, [ev("engine:step", 0, 2)])
    assert s.idle_by_span_s == pytest.approx({
        "engine:step": 2e-3, "harness:step": 2.5e-3, "none": 3.5e-3})
    assert s.idle_gaps[0] == ("engine:step", pytest.approx(4e-3))  # 0-4
    # a gap mostly under no span still takes the span over part of it
    assert s.idle_gaps[1] == ("harness:step", pytest.approx(4e-3))  # 6-10


def test_of_two_spans_that_start_together_the_shorter_is_inner():
    pieces = sp.innermost([ev("engine:step", 0, 10),
                           ev("engine:admit", 0, 4)], 0, 10 * MS)
    assert [(a / MS, b / MS, n) for a, b, n in pieces] == [
        (0, 4, "engine:admit"), (4, 10, "engine:step")]


def test_idle_is_averaged_over_devices():
    ops, notes, spans = _synthetic()
    ops["/device:TPU:1"] = [ev("fusion.9", 0, 60)]
    s = sp.reduce_events(ops, notes, spans)
    assert s.idle_by_span_s["harness:drain"] == pytest.approx(4e-3)
    assert sum(s.idle_by_span_s.values()) == \
        pytest.approx(s.window_s - s.busy_s)


def test_span_time_and_count():
    ops, notes, spans = _synthetic()
    s = sp.reduce_events(ops, notes, spans)
    assert s.span_count["engine:decode_dispatch"] == 2
    assert s.span_time_s["engine:step"] == pytest.approx(26e-3)
    assert s.span_count["harness:step"] == 2


def _summary(**kw):
    base = dict(window_s=1.0, busy_s=0.5, idle_by_span_s={},
                span_time_s={}, span_count={}, idle_gaps=[])
    return sp.SpanSummary(**dict(base, **kw))


def test_engine_idle_per_step():
    s = _summary(idle_by_span_s={"engine:decode_sync": 0.02,
                                 "engine:record": 0.01,
                                 "harness:step": 0.3, "none": 0.1},
                 span_count={"engine:decode_dispatch": 4})
    assert sp.engine_idle_ms_per_step(s) == pytest.approx(7.5)


def test_engine_host_per_step():
    s = _summary(span_time_s={"engine:step": 0.5,
                              "engine:decode_sync": 0.3,
                              "engine:prefill_sync": 0.1},
                 span_count={"engine:decode_dispatch": 10})
    assert sp.engine_host_ms_per_step(s) == pytest.approx(10.0)


@pytest.mark.parametrize("read", [sp.engine_idle_ms_per_step,
                                  sp.engine_host_ms_per_step])
def test_per_step_readings_are_silent_without_engine_spans(read):
    """A program without the spans, or a stretch with no decode step,
    reads nothing, and raises nothing."""
    s = _summary(idle_by_span_s={"harness:step": 0.5},
                 span_time_s={"harness:step": 1.0},
                 span_count={"harness:step": 3})
    assert read(s) is None


def test_a_trace_without_engine_spans():
    """The v5e trace recorded before the engine had spans: the same
    window and busy time as ``trace_reduce``, idle on harness spans."""
    path = str(TESTDATA / "tiny_v5e.xplane.pb.gz")
    s, base = sp.reduce_file(path), tr.reduce_file(path)
    assert s.window_s == base.window_s and s.busy_s == base.busy_s
    assert {k.split(":")[0] for k in s.idle_by_span_s} <= {"harness",
                                                           "none"}
    assert sum(s.idle_by_span_s.values()) == \
        pytest.approx(s.window_s - s.busy_s)
    assert sp.engine_idle_ms_per_step(s) is None


def test_a_recorded_v5e_trace_with_spans():
    """A trace recorded on a TPU v5e with the sink on
    (``testdata/record_spans_trace.py``): every phase of a step is in it
    under its exact name, and the idle inside ``harness:step`` goes to
    the engine's spans or to the annotation's own time."""
    path = str(TESTDATA / "tiny_v5e_spans.xplane.pb.gz")
    ops, _modules, notes = tr.load(path)
    spans = sp.load_spans(path)
    names = {e.name for e in spans}
    assert {"engine:step", "engine:admit", "engine:prefill",
            "engine:prefix_attach", "engine:prefill_chunk",
            "engine:prefill_sync", "engine:register_prefix", "engine:grow",
            "engine:decode_inputs", "engine:decode_dispatch",
            "engine:decode_sync", "engine:record"} == names
    steps = sorted((n.start_ns, n.end_ns) for n in notes
                   if n.name == "harness:step")
    lo = min(n.start_ns for n in notes)
    hi = max(n.end_ns for n in notes)
    pieces = sp.innermost(notes + spans, lo, hi)
    (dev,) = ops.values()
    busy = tr._clip(tr._union([(e.start_ns, e.end_ns) for e in dev]),
                    lo, hi)
    idle = [(a, b) for a, b in zip([lo] + [b for _, b in busy],
                                   [a for a, _ in busy] + [hi]) if b > a]
    in_step = [(max(a, sa), min(b, sb)) for a, b in idle
               for sa, sb in steps if min(b, sb) > max(a, sa)]
    by = sp.attribute(sorted(in_step), pieces)
    assert set(by) <= {n for n in names} | {"harness:step"}
    assert sum(v for k, v in by.items() if k.startswith("engine:")) > 0

    s = sp.reduce_file(path)
    want = json.loads((TESTDATA / "tiny_v5e_spans.summary.json")
                      .read_text())
    got = json.loads(json.dumps(dataclasses.asdict(s)))
    assert sum(s.idle_by_span_s.values()) == \
        pytest.approx(s.window_s - s.busy_s)
    assert got["span_count"] == want["spans"]["span_count"]
    assert got["span_time_s"] == pytest.approx(want["spans"]["span_time_s"])
    assert got["idle_by_span_s"] == \
        pytest.approx(want["spans"]["idle_by_span_s"])
    assert got["idle_gaps"] == want["spans"]["idle_gaps"]
    base = tr.reduce_file(path)
    assert (s.window_s, s.busy_s) == (base.window_s, base.busy_s)
    assert base.window_s == pytest.approx(want["window_s"])
    assert base.exe_launches == want["exe_launches"]
    # the paged step's executables read by name, one each
    steps = sorted(k.split("(")[0] for k in base.exe_launches
                   if k.startswith(("jit_paged_", "jit__lambda")))
    assert steps == ["jit_paged_decode", "jit_paged_prefill"]
    r = json.loads(json.dumps(sp.readings(s)))
    assert r["engine_idle_ms_per_step"] > 0
    assert r["engine_host_ms_per_step"] > 0
    assert r["device_idle_share"] == pytest.approx(100 * base.idle_share)
    assert r["idle_by_span_s"] == got["idle_by_span_s"]


def test_spans_py_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, str(HERE / "spans.py"), "--workload",
         "qwen2-1.5b.longctx", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr
