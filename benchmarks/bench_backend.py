"""Interpreter vs compiled-backend throughput on the four QNN workloads.

For each workload: run the optimized graph through the per-node numpy
interpreter (``Graph.execute``) and through the compiled backend
(``SiraModel.compile()`` — jitted JAX routed through the kernel wrappers;
jnp reference path on CPU, Pallas on TPU), on the same batched inputs,
and record per-sample latency + speedup.

The TFC row additionally carries the **tracer-overhead guard**: the
compiled path is re-timed with the ``repro.obs`` tracer enabled, and the
run aborts if enabled tracing costs more than 5% vs disabled — tracing
must never poison the dispatch-bound numbers (``trace_off_on_ratio`` is
gated in ``scripts/check_bench.py`` with a 0.95 hard floor).

Artifacts are routed through :func:`repro.obs.metrics.export_bench`, so
alongside ``BENCH_backend.json`` (schema unchanged) a Prometheus
text-format ``BENCH_backend.prom`` is written from the same registry.

    PYTHONPATH=src python benchmarks/bench_backend.py \
        [--batch 64] [--repeat 5] [--quick] [--out BENCH_backend.json] \
        [--trace trace_backend.json]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

#: workload whose compiled path carries the tracer-overhead guard — the
#: smallest (dispatch-bound) graph, where per-call overhead shows first
TRACE_GUARD_WORKLOAD = "TFC-w2a2"
TRACE_OVERHEAD_LIMIT = 1.05
#: the guard always measures at this batch regardless of --quick: the
#: 5% limit is calibrated against a ~100 us call — at tiny batches the
#: call shrinks toward pure dispatch and the same fixed tracer cost
#: would read as a limit-breaking percentage on every machine
TRACE_GUARD_BATCH = 64


def _time(fn, repeat: int) -> float:
    fn()                                 # warmup (trace/compile)
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _trace_overhead(compiled, feeds, repeat: int) -> float:
    """Best-of-N compiled-path time ratio disabled/enabled tracer.

    Returns ``disabled_s / enabled_s`` (1.0 = free, < 1.0 = enabled is
    slower).  The compiled TFC call is ~100 us, so best-of-small-N is
    noise; worse, a single disabled-then-enabled pass is one-sided — a
    scheduler blip during the enabled half reads as tracer overhead.
    Interleave several rounds and keep each side's global best."""
    from repro.obs.trace import disable_tracing, enable_tracing

    n = max(repeat, 20)
    disabled_s = enabled_s = float("inf")
    for _ in range(3):
        disabled_s = min(disabled_s, _time(lambda: compiled(feeds), n))
        tracer = enable_tracing()
        try:
            enabled_s = min(enabled_s, _time(lambda: compiled(feeds), n))
        finally:
            disable_tracing()
        del tracer
    return disabled_s / enabled_s


def bench_workload(name: str, batch: int, repeat: int) -> dict:
    from repro.core import build_flow
    from repro.core.workloads import WORKLOADS

    model = build_flow(WORKLOADS[name]()).model
    (inp,) = model.graph.inputs
    shape = (batch,) + tuple(model.metadata["input_shape"][1:])
    r = model.input_ranges[inp]
    rng = np.random.default_rng(0)
    lo = np.broadcast_to(np.asarray(r.lo, np.float64), shape)
    hi = np.broadcast_to(np.asarray(r.hi, np.float64), shape)
    x = rng.uniform(lo, hi, size=shape)
    feeds = {inp: x}

    interp_s = _time(lambda: model.execute(feeds), repeat)
    compiled = model.compile()
    compiled_s = _time(lambda: compiled(feeds), repeat)

    row = dict(
        workload=name,
        batch=batch,
        nodes=len(model.graph.nodes),
        plan=compiled.kernel_calls,
        interpreter_us_per_sample=interp_s / batch * 1e6,
        compiled_us_per_sample=compiled_s / batch * 1e6,
        speedup=interp_s / compiled_s,
    )
    if name == TRACE_GUARD_WORKLOAD:
        gshape = (TRACE_GUARD_BATCH,) + shape[1:]
        gfeeds = {inp: rng.uniform(np.broadcast_to(lo[:1], gshape),
                                   np.broadcast_to(hi[:1], gshape),
                                   size=gshape)}
        ratio = _trace_overhead(compiled, gfeeds, repeat)
        row["trace_off_on_ratio"] = ratio
        if ratio < 1.0 / TRACE_OVERHEAD_LIMIT:
            raise AssertionError(
                f"enabled-tracer overhead on the compiled {name} path is "
                f"{(1 / ratio - 1):.1%} (> "
                f"{TRACE_OVERHEAD_LIMIT - 1:.0%} limit) — the obs "
                f"instrumentation leaked work into the disabled hot path")
    return row


def _write_trace(path: str, workload: str, batch: int) -> None:
    """One fully traced flow+compile+call, exported as Chrome JSON."""
    from repro.core import build_flow
    from repro.core.workloads import WORKLOADS
    from repro.obs.trace import disable_tracing, enable_tracing

    tracer = enable_tracing()
    try:
        model = build_flow(WORKLOADS[workload]()).model
        compiled = model.compile()
        feeds = next(model.sample_inputs())
        compiled(feeds)
        tracer.write_chrome_trace(path)
    finally:
        disable_tracing()
    print(f"wrote {path} ({len(tracer.spans)} spans)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="small batch (CI smoke); keeps enough repeats "
                         "that best-of-N is stable — the tiny dispatch-"
                         "bound workloads (TFC) need ~20 samples for the "
                         "regression gate to be meaningful")
    ap.add_argument("--out", default="BENCH_backend.json")
    ap.add_argument("--trace", default=None, metavar="TRACE_JSON",
                    help="also run one fully traced TFC flow+compile+"
                         "call and write the Chrome trace_event JSON "
                         "(loadable in Perfetto) to this path")
    args = ap.parse_args()
    if args.quick:
        args.batch, args.repeat = 8, 20

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.core.workloads import WORKLOADS
    from repro.obs.metrics import export_bench

    results = []
    for name in WORKLOADS:
        row = bench_workload(name, args.batch, args.repeat)
        results.append(row)
        print(f"{name:10s} batch={row['batch']:3d} "
              f"interp={row['interpreter_us_per_sample']:9.1f} us/sample "
              f"compiled={row['compiled_us_per_sample']:9.1f} us/sample "
              f"speedup={row['speedup']:6.1f}x", flush=True)
    import jax
    payload = dict(backend=jax.default_backend(),
                   batch=args.batch, repeat=args.repeat,
                   results=results)
    export_bench(payload, args.out, key=("workload",))
    print(f"wrote {args.out} (+ Prometheus text next to it)")
    if args.trace:
        _write_trace(args.trace, TRACE_GUARD_WORKLOAD, args.batch)


if __name__ == "__main__":
    main()
