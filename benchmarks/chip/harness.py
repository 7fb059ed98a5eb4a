"""One run of one cell: set-up, the measured window, the drain, the check.

Set-up builds the engine the window drives (``ServingEngine`` over
``Model.decode_paged``) with weights made from the seed on the device,
warms every shape the cell's traffic uses (the decode step over all
slots, the prefill chunk, sampling after each, the copy of a prefix's
boundary page) and serves the set-up requests the traffic needs.  The
window then feeds the engine through ``submit`` and ``step`` alone, on
an open-loop schedule or a closed loop, for ``seconds``.  After it the
engine drains the window's requests, the device's peak memory is read,
the engine's state is freed, and a sample of the finished requests is
compared with the plain float32 reference (``check``).

With ``trace=True`` part of the window runs under the profiler and the
per-layer metrics are read from that trace and from the engine's
counters; otherwise the end-to-end metrics are reported.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.chip import check, e2e, spec, trace_reduce, weights
from benchmarks.chip.peaks import peaks

#: the drain waits for every window request at least this many seconds
#: past the window's close, and on until ``DRAIN_UNTIL_S`` after the
#: process started: a run has 360 s, and the rest is left for the
#: reference and the reduction of the trace
DRAIN_S, DRAIN_UNTIL_S = 60.0, 270.0
#: longest traced stretch of a window, seconds (at published widths a
#: second of serving is some 7 MB of trace)
TRACE_S = 5.0
#: warm-up request: a prompt that spans several pages and chunks, and a
#: second that shares a prime number of its tokens, so attaching it
#: copies a boundary page whatever the page size
WARM_PROMPT, WARM_SHARED, WARM_NEW = 53, 37, 2


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


@dataclasses.dataclass
class ReqRecord:
    handle: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float
    shared_prefix: int
    sent: float                        # perf_counter at submit
    due: float                         # perf_counter it was due
    in_window: bool = False            # due (open) / admitted (closed)
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    finished: bool = False


@dataclasses.dataclass
class Records:
    """What a run recorded; the metric readers take this."""
    cell: Any
    process_start: float
    window_start: float
    window_s: float
    drain_end: float
    requests: List[ReqRecord]
    counters: Dict[str, int]           # engine counters over the window
    trace: Optional[trace_reduce.TraceSummary] = None
    trace_span: Optional[tuple] = None          # perf_counter (t0, t1)
    exe_labels: Dict[str, str] = dataclasses.field(default_factory=dict)
    peaks: Optional[Dict[str, float]] = None


class CompileCounter:
    """Counts executables that JAX lowers (compiled or loaded from the
    persistent cache): none should appear inside the window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax._src import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.n += 1


def counters(eng) -> Dict[str, int]:
    m = eng.metrics
    return dict(decode_steps=m.decode_steps, prefill_chunks=m.prefill_chunks,
                active_slot_steps=m.active_slot_steps,
                slot_capacity=m.slot_capacity, preemptions=m.preemptions,
                prefix_hit_tokens=m.prefix_hit_tokens,
                prefix_lookup_tokens=m.prefix_lookup_tokens,
                tokens=sum(r.n_tokens for r in m.requests.values()))


def start_profiler(log_dir: str) -> None:
    """The profiler without its Python tracer, whose events of every
    Python call in the serving loop no reader takes."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


class _Tracing:
    """Starts and stops the profiler once, at set times in the window."""

    def __init__(self, on: bool, start: float, stop: float):
        self.on, self.start, self.stop = on, start, stop
        self.state = "before"
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.span = None

    def tick(self, now: float) -> None:
        if not self.on:
            return
        if self.state == "before" and now >= self.start:
            start_profiler(self.dir)
            self.state, self.span = "on", (time.perf_counter(), None)
        elif self.state == "on" and now >= self.stop:
            self.finish()

    def finish(self) -> None:
        import jax
        if self.state == "on":
            self.span = (self.span[0], time.perf_counter())
            jax.profiler.stop_trace()
            self.state = "done"
            log(f"trace: stop_trace held the loop "
                f"{time.perf_counter() - self.span[1]:.2f} s")


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _submit(eng, req, due: float) -> ReqRecord:
    from repro.serve import Request
    with _annotate("harness:submit"):
        h = eng.submit(Request(prompt=req.prompt,
                               max_new_tokens=req.max_new_tokens,
                               temperature=req.temperature))
    return ReqRecord(handle=h, prompt=req.prompt,
                     max_new_tokens=req.max_new_tokens,
                     temperature=req.temperature,
                     shared_prefix=req.shared_prefix,
                     sent=time.perf_counter(), due=due)


def _step(eng, note: str = "harness:step") -> None:
    with _annotate(note):
        eng.step()


def _done(eng, r: ReqRecord) -> bool:
    rec = eng.metrics.requests.get(r.handle)
    return rec is not None and rec.finish_t is not None


def drive_open(eng, mix, seconds: float, tracing: _Tracing):
    """Submit each request at its due time, whatever the engine does."""
    due = mix.due_s
    recs: List[ReqRecord] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    k = 0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        tracing.tick(now)
        while k < len(due) and t0 + due[k] <= now:
            recs.append(_submit(eng, mix.request(k), t0 + due[k]))
            recs[-1].in_window = True
            k += 1
        if eng.scheduler.has_work():
            _step(eng)
        else:
            nxt = t0 + due[k] if k < len(due) else end
            with _annotate("harness:wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
    # requests due before the close that a long step kept from being sent
    while k < len(due) and due[k] < seconds:
        recs.append(_submit(eng, mix.request(k), t0 + due[k]))
        recs[-1].in_window = True
        k += 1
    return t0, recs


def drive_closed(eng, mix, seconds: float, tracing: _Tracing):
    """Keep ``mix.backlog`` requests outstanding; a request counts for
    the window when it is admitted into a slot before the close."""
    recs: List[ReqRecord] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    outstanding: List[ReqRecord] = []
    k = 0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        tracing.tick(now)
        while len(outstanding) < mix.backlog:
            r = _submit(eng, mix.request(k), time.perf_counter())
            k += 1
            recs.append(r)
            outstanding.append(r)
        _step(eng)
        # every step here began before the close, so whatever it admitted
        # was admitted in the window
        live = {s.entry.handle for s in eng.scheduler.slots
                if s is not None}
        for r in outstanding:
            if r.handle in live or eng.metrics.requests[
                    r.handle].token_times:
                r.in_window = True
        outstanding = [r for r in outstanding if not _done(eng, r)]
    return t0, recs


def drain_deadline(window_end: float, process_start: float) -> float:
    """When the drain stops waiting: ``DRAIN_S`` past the window's close,
    or ``DRAIN_UNTIL_S`` after the process started, whichever is later."""
    return max(window_end + DRAIN_S, process_start + DRAIN_UNTIL_S)


def drain(eng, recs: List[ReqRecord], deadline: float) -> float:
    """Step until every window request has finished, or the deadline.
    A request that is late is waited for, not judged wrong."""
    while any(r.in_window and not _done(eng, r) for r in recs) and \
            time.perf_counter() < deadline and eng.scheduler.has_work():
        _step(eng, "harness:drain")
    return time.perf_counter()


def warm_up(eng, mix, vocab: int, seed: int) -> None:
    """Compile and run every shape the window will use, then serve the
    set-up requests of the traffic (e.g. documents into the prefix
    cache)."""
    from repro.serve import Request
    from benchmarks.chip import sampling

    toks = sampling.rng(seed, 1000).integers(0, vocab, 2 * WARM_PROMPT)
    a = toks[:WARM_PROMPT]
    b = np.concatenate([a[:WARM_SHARED], toks[WARM_PROMPT:][:WARM_PROMPT
                                                             - WARM_SHARED]])
    for p in (a, b):
        eng.generate([Request(prompt=p.astype(np.int32),
                              max_new_tokens=WARM_NEW)])
    for r in mix.setup:
        eng.generate([Request(prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens,
                              temperature=r.temperature)])


def identify_executables(eng, vocab: int, seed: int) -> Dict[str, str]:
    """Trace a request that is only prefilled, then one that decodes
    too, and name the executables by the trace's keys: the prefill step
    is the busiest in the first trace, the decode step the busiest of
    those that only the second holds."""
    import jax
    from repro.serve import Request
    from benchmarks.chip import sampling

    def exe_times(max_new: int):
        d = tempfile.mkdtemp(prefix="bench_ident_")
        try:
            p = sampling.rng(seed, 1001, max_new).integers(0, vocab,
                                                           WARM_PROMPT)
            start_profiler(d)
            with _annotate("harness:identify"):
                eng.generate([Request(prompt=p.astype(np.int32),
                                      max_new_tokens=max_new)])
            jax.profiler.stop_trace()
            s = trace_reduce.reduce_file(trace_reduce.find_trace(d))
            return s.exe_time_s if s else {}
        finally:
            shutil.rmtree(d, ignore_errors=True)

    first = exe_times(1)
    if not first:
        return {}
    labels = {"prefill": max(first, key=first.get)}
    second = {k: v for k, v in exe_times(8).items() if k not in first}
    if second:
        labels["decode"] = max(second, key=second.get)
    return labels


def device_info(peak_bytes: Optional[int]) -> Dict[str, Any]:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": peak_bytes}


def _peak_bytes() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


@dataclasses.dataclass
class Session:
    """A cell set up for one seed: the engine the window drives."""
    cell: Any
    seed: int
    arch: Any
    w: Dict[str, Any]                  # the seeded weights
    eng: Any
    mix: Any
    labels: Dict[str, str]


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def setup(cell: "spec.Cell", *, seed: int, seconds: float,
          trace: bool) -> Session:
    """Weights from the seed, the engine, the traffic, and the warm-up."""
    from repro.models import get_model
    from repro.serve import ServingConfig, ServingEngine

    compile_counter()
    phase = {"start": time.perf_counter()}
    config = cell.config
    kind = spec.arch_kind(cell)
    arch = kind.arch_config(config)
    model = get_model(arch)
    w = weights.make(kind, config, arch.vocab_padded, seed, arch.dtype)
    params = kind.to_program(w, model)
    phase["weights"] = time.perf_counter()
    eng = ServingEngine(model, params, ServingConfig(**config["engine"]))
    phase["engine"] = time.perf_counter()
    mix = spec.traffic_kind(cell).make(
        cell.traffic, seed=seed, vocab=arch.vocab, seconds=seconds,
        cell_params=cell.params, engine=config["engine"])
    warm_up(eng, mix, arch.vocab, seed)
    labels = identify_executables(eng, arch.vocab, seed) if trace else {}
    phase["warm"] = time.perf_counter()
    log(f"SIRA int8 KV layers: {eng.kv_spec.n_int8} of {arch.n_layers}")
    log("set-up phases (s): " + ", ".join(
        f"{k} {phase[k] - phase['start']:.2f}"
        for k in ("weights", "engine", "warm")))
    if labels:
        log(f"executables: {labels}")
    return Session(cell=cell, seed=seed, arch=arch, w=w, eng=eng, mix=mix,
                   labels=labels)


def measure(ses: Session, *, seconds: float, trace: bool,
            process_start: float) -> Records:
    """The window and its drain; with ``trace``, the reduced trace."""
    eng, mix = ses.eng, ses.mix
    eng.reset_metrics()
    gc.collect()
    start = seconds / 3 if trace else float("inf")
    counter = compile_counter()
    n_compiles = counter.n
    t_now = time.perf_counter()
    tracing = _Tracing(trace, t_now + start,
                       t_now + start + min(TRACE_S, seconds / 3))
    drive = drive_open if mix.loop == "open" else drive_closed
    t0, recs = drive(eng, mix, seconds, tracing)
    tracing.finish()
    window_counters = counters(eng)
    in_window = counter.n - n_compiles
    drain_end = drain(eng, recs, drain_deadline(t0 + seconds,
                                                process_start))
    for r in recs:
        r.tokens = list(eng.scheduler.outputs.get(r.handle, []))
        mrec = eng.metrics.requests.get(r.handle)
        r.token_times = list(mrec.token_times) if mrec else []
        r.finished = _done(eng, r)
    log(f"drain: {drain_end - t0 - seconds:.2f} s past the close, "
        f"scheduler has work: {eng.scheduler.has_work()}")
    for r in recs:
        if r.in_window and not r.finished:
            first = (f"{r.token_times[0] - t0:.2f}" if r.token_times
                     else "none")
            log(f"unfinished: due {r.due - t0:.2f} s, sent {r.sent - t0:.2f}"
                f" s, prompt {len(r.prompt)}, tokens {len(r.tokens)} of "
                f"{r.max_new_tokens}, first token at {first} s")
    late = [r.sent - r.due for r in recs if r.in_window]
    log(f"compiles_in_window: {in_window}")
    log(f"window: {sum(r.in_window for r in recs)} requests, generator "
        f"late p50 {1e3 * np.median(late) if late else 0:.3f} ms, max "
        f"{1e3 * max(late) if late else 0:.3f} ms; counters "
        f"{window_counters}")
    rec = Records(cell=ses.cell, process_start=process_start,
                  window_start=t0, window_s=seconds, drain_end=drain_end,
                  requests=recs, counters=window_counters,
                  exe_labels=ses.labels)
    if trace:
        path = trace_reduce.find_trace(tracing.dir) if tracing.dir else None
        rec.trace = trace_reduce.reduce_file(path) if path else None
        shutil.rmtree(tracing.dir, ignore_errors=True)
        rec.trace_span = tracing.span
    return rec


def release(ses: Session) -> None:
    """Free the engine's device state (the weights stay, for the
    reference)."""
    ses.eng.cache.pages = None
    ses.eng = None
    gc.collect()


def run_cell(cell: "spec.Cell", *, seed: int, seconds: float, trace: bool,
             process_start: float) -> Dict[str, Any]:
    """One run; returns the result object that ``run.py`` prints."""
    ses = setup(cell, seed=seed, seconds=seconds, trace=trace)
    rec = measure(ses, seconds=seconds, trace=trace,
                  process_start=process_start)
    device = device_info(_peak_bytes())
    if trace:
        rec.peaks = peaks(device["kind"]) if device["platform"] == "tpu" \
            else None
        if rec.trace is not None:
            device["busy_s"] = rec.trace.busy_s
            device["window_s"] = rec.trace.window_s
    # the reference runs beside the weights once the engine's state is freed
    release(ses)
    t_check = time.perf_counter()
    verdict = check.judge(rec, ses.w, seed)
    log(f"check took {time.perf_counter() - t_check:.2f} s")

    recs = rec.requests
    result: Dict[str, Any] = {
        "correct": verdict["correct"],
        "attempted": sum(r.in_window for r in recs),
        "failed": sum(r.in_window and not r.finished for r in recs),
        "metrics": {}, "device": device}
    if trace:
        for m in cell.per_layer:
            v = spec.metric_reader(cell, m)(rec)
            if v is not None:
                result["metrics"][m.name] = {"value": v, "unit": m.unit}
        if rec.trace is not None:
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in rec.trace.top_ops],
                "idle_gaps": [[n, s] for n, s in rec.trace.idle_gaps]}
    else:
        for m in cell.end_to_end:
            v = e2e.METRICS[m.name](rec)
            if v is not None:
                result["metrics"][m.name] = {"value": v, "unit": m.unit}
    log(f"compared {verdict['tokens']} served tokens of "
        f"{verdict['requests']} requests")
    result["check"] = verdict["numbers"]
    for name, nl in verdict["numbers"].items():
        log(f"check {name}: {nl['value']!r} (limit {nl['limit']!r})")
    return result
