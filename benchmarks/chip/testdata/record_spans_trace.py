"""Record ``tiny_v5e_spans.xplane.pb.gz``: the run that
``record_trace.py`` traces, with the program's tracer sink on, so the
engine's ``engine:*`` spans are in the trace beside the harness's
annotations; and ``tiny_v5e_spans.summary.json``, what ``trace_reduce``
and ``spans`` read from it then.

Run on a machine with a TPU, from the checkout's root:

    python3 benchmarks/chip/testdata/record_spans_trace.py OUT_DIR
"""
import dataclasses
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> None:
    import jax
    from repro.obs.trace import disable_tracing, enable_tracing

    from benchmarks.chip import spans, trace_reduce
    from benchmarks.chip.testdata import record_trace

    tmp = tempfile.mkdtemp()
    enable_tracing(annotate=jax.profiler.TraceAnnotation)
    try:
        record_trace.main(tmp)
    finally:
        disable_tracing()
    Path(out).mkdir(parents=True, exist_ok=True)
    pb = Path(out) / "tiny_v5e_spans.xplane.pb.gz"
    shutil.copy(Path(tmp) / "tiny_v5e.xplane.pb.gz", pb)
    summary = dataclasses.asdict(trace_reduce.reduce_file(str(pb)))
    summary["spans"] = dataclasses.asdict(spans.reduce_file(str(pb)))
    (Path(out) / "tiny_v5e_spans.summary.json").write_text(
        json.dumps(summary, indent=1))
    print(pb, pb.stat().st_size)


if __name__ == "__main__":
    main(sys.argv[1])
