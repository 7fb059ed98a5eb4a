"""Run one cell of the on-chip benchmark and print its result.

    python3 benchmarks/chip/run.py --workload qwen2-1.5b.chat \\
        --seed 1234 --seconds 51 --trace 0

Runs on the machine it is started on, which must hold a TPU with as
many chips as the cell asks for; anywhere else it exits with code 2 and
prints no result.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, the
device's busy and traced seconds and a ``breakdown``.  The last line of
standard output is the result, one JSON object; the numbers the check
compared, each beside its limit, are the last lines of standard error
and the result's last key.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def chip_problem(chips: int):
    """Why this machine cannot run a cell on ``chips`` chips, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX found {devs[0].platform} devices"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX found {len(devs)}"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.chip import harness, spec

    cell = spec.load_cell(args.workload)
    problem = chip_problem(cell.chips)
    if problem:
        print(f"run.py: {problem}", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    # every program the window uses is kept, however fast it compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace),
                              process_start=PROCESS_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
