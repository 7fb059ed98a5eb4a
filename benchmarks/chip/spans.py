"""Put the device's idle time down to the serving engine's spans.

With the program's tracer sink on
(``enable_tracing(annotate=jax.profiler.TraceAnnotation)`` from
``repro.obs.trace``), every phase of a serving step is an ``engine:*``
span in the profiler's ``.xplane.pb``, on the clock of the device's ops
and beside the harness's ``harness:*`` annotations.  From such a trace:

* ``idle_by_span_s``: every idle nanosecond of the window, on each
  device, goes to the innermost span open at that moment, the one that
  started last among those open (``harness:*`` or ``engine:*``), and to
  ``none`` where no span is open; averaged over the devices, the values
  add up to the window less the device's busy time;
* ``span_time_s``, ``span_count``: the total time and the count, per
  name, of the spans that start in the window;
* ``idle_gaps``: the longest idle gaps, each labelled with the span that
  most of it went to.

The window, the busy time and the gaps are ``trace_reduce``'s: from the
first to the last ``harness:*`` annotation, so the engine's spans never
move them.

Run as a script on a machine with a TPU, it sets up a cell as the
benchmark does, turns the sink on for the benchmark's traced stretch of
the window, and prints the stretch's attribution as one JSON line:

    python3 benchmarks/chip/spans.py --workload qwen2-1.5b.longctx \\
        --seed 1234 --seconds 51

``run.py`` never turns the sink on.
"""
from __future__ import annotations

import dataclasses
import heapq
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import trace_reduce  # noqa: E402
from benchmarks.chip.trace_reduce import Ev  # noqa: E402

#: the serving engine's spans (``serve/engine.py``)
SPAN_PREFIX = "engine:"
#: where idle time under no span goes
NO_SPAN = "none"

Piece = Tuple[float, float, str]


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    busy_s: float                      # averaged over devices
    idle_by_span_s: Dict[str, float]   # averaged over devices
    span_time_s: Dict[str, float]
    span_count: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]


def innermost(spans: Sequence[Ev], lo: float, hi: float) -> List[Piece]:
    """``[lo, hi]`` cut into pieces ``(start, end, name)``, each named
    after the innermost span open over it: of the spans that cover it,
    the one that started last (of two that started together, the one
    that ends first); :data:`NO_SPAN` where none does."""
    cuts = sorted({lo, hi} | {x for s in spans
                              for x in (s.start_ns, s.end_ns) if lo < x < hi})
    order = sorted((s for s in spans if s.end_ns > lo and s.start_ns < hi),
                   key=lambda s: s.start_ns)
    heap: List[Tuple[float, float, int, str]] = []
    out: List[Piece] = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(order) and order[j].start_ns <= a:
            s = order[j]
            heapq.heappush(heap, (-s.start_ns, s.end_ns, j, s.name))
            j += 1
        while heap and heap[0][1] <= a:        # the innermost has ended
            heapq.heappop(heap)
        out.append((a, b, heap[0][3] if heap else NO_SPAN))
    return out


def attribute(gaps: Sequence[Tuple[float, float]],
              pieces: Sequence[Piece]) -> Dict[str, float]:
    """Nanoseconds of the gaps (sorted, disjoint) under each piece's
    name."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            ov = min(b, pb) - max(a, pa)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            k += 1
    return out


def label(gap: Tuple[float, float], pieces: Sequence[Piece]) -> str:
    """The span that most of a gap went to; :data:`NO_SPAN` only where
    no span covers any of it."""
    by = attribute([gap], pieces)
    by.pop(NO_SPAN, None)
    return max(by, key=by.get) if by else NO_SPAN


def reduce_events(ops: Dict[str, List[Ev]], notes: List[Ev],
                  spans: Sequence[Ev]) -> SpanSummary:
    """``ops``: device name -> its op events; ``notes``: the harness's
    annotations, which bound the window; ``spans``: the engine's."""
    if notes:
        lo = min(n.start_ns for n in notes)
        hi = max(n.end_ns for n in notes)
    else:
        every = [e for evs in ops.values() for e in evs]
        lo = min(e.start_ns for e in every)
        hi = max(e.end_ns for e in every)
    every_span = list(notes) + list(spans)
    pieces = innermost(every_span, lo, hi)
    busy_ns, gaps = 0.0, []
    idle_by: Dict[str, float] = {}
    for evs in ops.values():
        iv = trace_reduce._clip(
            trace_reduce._union([(e.start_ns, e.end_ns) for e in evs]),
            lo, hi)
        busy_ns += sum(b - a for a, b in iv)
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        dev_gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a]
        for name, ns in attribute(dev_gaps, pieces).items():
            idle_by[name] = idle_by.get(name, 0.0) + ns
        gaps += dev_gaps
    n_dev = max(len(ops), 1)
    span_t: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for e in every_span:
        if lo <= e.start_ns < hi:
            span_t[e.name] = span_t.get(e.name, 0.0) + e.dur_ns * 1e-9
            span_n[e.name] = span_n.get(e.name, 0) + 1
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    return SpanSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n_dev,
        idle_by_span_s={k: v * 1e-9 / n_dev for k, v in idle_by.items()},
        span_time_s=span_t, span_count=span_n,
        idle_gaps=[(label(g, pieces), (g[1] - g[0]) * 1e-9)
                   for g in longest])


def load_spans(path: str) -> List[Ev]:
    """The engine's spans from an ``.xplane.pb`` file (or its gzip)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path) as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(str(path))
    return [trace_reduce._ev(e) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def reduce_file(path: str) -> Optional[SpanSummary]:
    """The span summary of one trace file; None where no device op ran."""
    ops, _modules, notes = trace_reduce.load(path)
    if not any(ops.values()):
        return None
    return reduce_events(ops, notes, load_spans(path))


def readings(s: SpanSummary) -> Dict[str, object]:
    """The summary with the device's idle share and the per-step
    readings, as one JSON-ready object."""
    return dict(dataclasses.asdict(s),
                device_idle_share=100.0 * (1.0 - s.busy_s / s.window_s),
                engine_idle_ms_per_step=engine_idle_ms_per_step(s),
                engine_host_ms_per_step=engine_host_ms_per_step(s))


def engine_idle_ms_per_step(s: SpanSummary) -> Optional[float]:
    """Device idle while the host was in engine code, per decode step."""
    steps = s.span_count.get("engine:decode_dispatch", 0)
    if not steps:
        return None
    idle = sum(v for k, v in s.idle_by_span_s.items()
               if k.startswith(SPAN_PREFIX))
    return 1e3 * idle / steps


def engine_host_ms_per_step(s: SpanSummary) -> Optional[float]:
    """The engine's host time per decode step outside its waits on the
    device."""
    steps = s.span_count.get("engine:decode_dispatch", 0)
    if not steps:
        return None
    t = s.span_time_s
    busy = (t.get("engine:step", 0.0) - t.get("engine:decode_sync", 0.0)
            - t.get("engine:prefill_sync", 0.0))
    return 1e3 * busy / steps


# ----------------------------------------------------------------- script

def main(argv=None) -> int:
    import argparse
    import gc
    import json
    import time

    process_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.obs.trace import disable_tracing, enable_tracing
    from benchmarks.chip import harness, spec, work

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("spans.py: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    class SinkTracing(harness._Tracing):
        """The harness's traced stretch, with the engine's spans in it."""

        hold = None                    # seconds stop_trace held the loop

        def tick(self, now: float) -> None:
            before = self.state
            super().tick(now)
            if before == "before" and self.state == "on":
                enable_tracing(annotate=jax.profiler.TraceAnnotation)

        def finish(self) -> None:
            disable_tracing()
            super().finish()
            if self.hold is None and self.state == "done":
                self.hold = time.perf_counter() - self.span[1]

    ses = harness.setup(cell, seed=args.seed, seconds=args.seconds,
                        trace=True)
    eng, mix, seconds = ses.eng, ses.mix, args.seconds
    eng.reset_metrics()
    gc.collect()
    t_now = time.perf_counter()
    tracing = SinkTracing(True, t_now + seconds / 3,
                          t_now + seconds / 3 + min(harness.TRACE_S,
                                                    seconds / 3))
    drive = harness.drive_open if mix.loop == "open" else harness.drive_closed
    drive(eng, mix, seconds, tracing)
    tracing.finish()
    path = trace_reduce.find_trace(tracing.dir)
    rec = harness.Records(
        cell=cell, process_start=process_start, window_start=0.0,
        window_s=seconds, drain_end=0.0, requests=[],
        counters=harness.counters(eng), exe_labels=ses.labels,
        trace=trace_reduce.reduce_file(path))
    s = reduce_file(path)
    d = jax.devices()[0]
    out = {"workload": cell.name, "seed": args.seed,
           "device": {"platform": d.platform, "kind": d.device_kind},
           "stop_trace_hold_s": tracing.hold,
           "decode_step_device_ms": work.exe_ms(rec, "decode"),
           "prefill_step_device_ms": work.exe_ms(rec, "prefill"),
           "exe_labels": ses.labels}
    if s is not None:
        out.update(readings(s))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
