"""The serving engine's spans in a profiler's own trace, and its named
jitted steps.

With the tracer's profiler sink on, every phase of a paged step is an
``engine:*`` event in the ``.xplane.pb``, nested in whatever the caller
annotated around ``step()``; with the tracer disabled nothing is
recorded and nothing reaches a sink.  The shared paged step runs as
three jits of their own names, each traced for one shape.
"""
import glob

import jax
import numpy as np
import pytest
from jax._src import monitoring

from repro.configs import get_config
from repro.models import get_model
from repro.obs import (NULL_SPAN, Tracer, disable_tracing, enable_tracing,
                       get_tracer, set_tracer)
from repro.serve import FixedDrafter, Request, ServingConfig, ServingEngine

#: one span per phase of a paged step (``serve/engine.py``)
PHASES = {"engine:step", "engine:admit", "engine:prefill",
          "engine:prefix_attach", "engine:prefill_chunk",
          "engine:prefill_sync", "engine:register_prefix", "engine:grow",
          "engine:decode_inputs", "engine:decode_dispatch",
          "engine:decode_sync", "engine:record"}
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b", reduced=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, lengths=(11, 9, 19), max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=max_new) for n in lengths]


def _host_events(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("engine:", "harness:"))]


def _serve_profiled(eng, reqs, log_dir):
    """Submit and step to the end under the profiler with the sink on,
    each step inside a ``harness:step`` annotation."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    steps0 = eng.metrics.decode_steps
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        enable_tracing(annotate=jax.profiler.TraceAnnotation)
        for r in reqs:
            eng.submit(r)
        while eng.scheduler.has_work():
            with jax.profiler.TraceAnnotation("harness:step"):
                eng.step()
    finally:
        disable_tracing()
        jax.profiler.stop_trace()
    return eng.metrics.decode_steps - steps0


def _check_nested(events):
    steps = [(a, b) for n, a, b in events if n == "harness:step"]
    for name, a, b in events:
        if name.startswith("engine:"):
            assert any(sa <= a and b <= sb for sa, sb in steps), name


def test_engine_phases_land_in_the_profile_nested_in_the_caller(
        setup, tmp_path):
    cfg, model, params = setup
    eng = ServingEngine(model, params, ServingConfig(
        batch_slots=2, max_seq=64, prefill_chunk=8, prefix_cache=True))
    eng.generate(_requests(cfg, seed=1))          # compile outside
    n_steps = _serve_profiled(eng, _requests(cfg), tmp_path)
    events = _host_events(tmp_path)
    names = {n for n, _, _ in events}
    assert PHASES <= names
    assert {n for n in names if n.startswith("engine:")} == PHASES
    _check_nested(events)
    dispatches = [e for e in events if e[0] == "engine:decode_dispatch"]
    assert n_steps > 0 and len(dispatches) == n_steps
    # three prompts of 11, 9 and 19 tokens in chunks of 8
    assert sum(n == "engine:prefill_chunk" for n, _, _ in events) == 7
    assert sum(n == "engine:prefill" for n, _, _ in events) == 3


def test_speculative_phases_land_in_the_profile(setup, tmp_path):
    cfg, model, params = setup
    eng = ServingEngine(model, params, ServingConfig(
        batch_slots=2, max_seq=64, spec_decode=FixedDrafter([1, 2, 3]),
        spec_k=3))
    eng.generate(_requests(cfg, seed=1, max_new=6))
    n_steps = _serve_profiled(eng, _requests(cfg, max_new=6), tmp_path)
    events = _host_events(tmp_path)
    names = {n for n, _, _ in events}
    assert {"engine:spec_propose", "engine:grow", "engine:decode_inputs",
            "engine:decode_dispatch", "engine:decode_sync",
            "engine:record"} <= names
    _check_nested(events)
    assert n_steps == sum(n == "engine:decode_dispatch"
                          for n, _, _ in events)
    assert eng.metrics.spec_proposed > 0


def test_a_disabled_tracer_records_nothing_and_calls_no_sink(setup):
    cfg, model, params = setup
    calls = []

    def sink(name, **attrs):
        calls.append(name)
        raise AssertionError("a disabled tracer reached its sink")

    prev = set_tracer(Tracer(enabled=False, annotate=sink))
    try:
        eng = ServingEngine(model, params,
                            ServingConfig(batch_slots=2, max_seq=64))
        eng.generate(_requests(cfg))
        assert get_tracer().spans == [] and calls == []
        assert get_tracer().span("engine:step") is NULL_SPAN
    finally:
        set_tracer(prev)
    disable_tracing()
    assert get_tracer().span("engine:step") is NULL_SPAN


@pytest.mark.parametrize("spec, want", [
    (None, {"paged_prefill": 1, "paged_decode": 1}),
    (FixedDrafter([1, 2, 3]), {"paged_prefill": 1, "paged_verify": 1})])
def test_each_named_step_compiles_once(setup, spec, want):
    """Each of the paged step's jits is lowered once, for one shape, under
    its own name: as many executables as the one shared ``jit__lambda``
    compiled before (a prefill chunk and a decode step, or a verify
    window)."""
    cfg, model, params = setup
    lowered = []

    def on_event(name, _secs, fun_name="", **_kw):
        if name == COMPILE_EVENT:
            lowered.append(fun_name)

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        eng = ServingEngine(model, params, ServingConfig(
            batch_slots=2, max_seq=64, prefill_chunk=8, spec_decode=spec,
            spec_k=3))
        eng.generate(_requests(cfg, lengths=(5, 13, 21, 30), max_new=6))
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    steps = {n[4:-1]: lowered.count(n) for n in set(lowered)
             if n.startswith("jit(paged_")}
    # the last token of a request has no draft, so a step in which every
    # slot is on its last token falls back to the decode step
    if spec is not None:
        assert steps.pop("paged_decode", 0) <= 1
    assert steps == want
    assert not any("lambda" in n for n in lowered)
