"""Record ``tiny_v5e.xplane.pb.gz``, the small TPU trace that the trace
reduction is tested on, and its summary as the reduction read it then.

Run on a machine with a TPU, from the checkout's root:

    python3 benchmarks/chip/testdata/record_trace.py OUT_DIR

A two-layer engine at toy widths serves two requests under the
profiler, inside the harness's own annotations, so the trace holds the
device plane, the ``XLA Modules`` and ``XLA Ops`` lines and the host's
``harness:*`` events that a benchmark run has, in a few hundred kB
once gzipped.
"""
import dataclasses
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import get_model
    from repro.serve import Request, ServingConfig, ServingEngine

    from benchmarks.chip import trace_reduce

    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    cfg = dataclasses.replace(get_config("qwen2-1.5b", reduced=True),
                              dtype=jnp.bfloat16)
    model = get_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        ServingConfig(batch_slots=2, max_seq=64))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=3) for n in (11, 9)]
    eng.generate(reqs)                          # compile outside the trace
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for r in reqs:
        with jax.profiler.TraceAnnotation("harness:submit"):
            eng.submit(r)
    with jax.profiler.TraceAnnotation("harness:step"):
        eng.step()
    with jax.profiler.TraceAnnotation("harness:wait_arrival"):
        time.sleep(0.005)
    with jax.profiler.TraceAnnotation("harness:drain"):
        eng.run()
    jax.profiler.stop_trace()
    Path(out).mkdir(parents=True, exist_ok=True)
    pb = Path(out) / "tiny_v5e.xplane.pb.gz"
    with open(trace_reduce.find_trace(d), "rb") as src, \
            gzip.open(pb, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    s = trace_reduce.reduce_file(str(pb))
    (Path(out) / "tiny_v5e.summary.json").write_text(json.dumps(
        dataclasses.asdict(s), indent=1))
    print(pb, pb.stat().st_size)


if __name__ == "__main__":
    main(sys.argv[1])
