"""Find the highest request rate the engine sustains on an open-loop
cell, once, by a sweep on the chip.

For each rate, lowest first, a fresh engine and a window of
``--seconds`` on the cell's mix at that rate, then its drain; the sweep
stops after two rates in a row that are not sustained.  A rate is
sustained when the queue does not grow through the window: at the close
no more requests wait for their first token than the engine has slots,
and the median time to first token of the window's last third is under
twice that of its first third.

    python3 benchmarks/chip/sweep.py --workload qwen2-1.5b.chat \\
        --seconds 40 --rates 1.0 1.5 2.0 2.5 3.0 --seed 5
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np
    from benchmarks.chip import e2e, harness, spec
    from benchmarks.chip.run import chip_problem

    cell = spec.load_cell(args.workload)
    problem = chip_problem(cell.chips)
    if problem:
        print(f"sweep.py: {problem}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    misses = 0
    for rate in sorted(args.rates):
        rcell = dataclasses.replace(cell, params=dict(cell.params,
                                                      rate_hz=rate))
        ses = harness.setup(rcell, seed=args.seed, seconds=args.seconds,
                            trace=False)
        rec = harness.measure(ses, seconds=args.seconds, trace=False,
                              process_start=PROCESS_START)
        slots = ses.eng.B
        harness.release(ses)
        close = rec.window_start + rec.window_s
        win = sorted((r for r in rec.requests if r.in_window),
                     key=lambda r: r.due)
        waiting = sum(1 for r in win
                      if not r.token_times or r.token_times[0] >= close)
        third = max(len(win) // 3, 1)
        ttft = [(r.token_times[0] if r.token_times else rec.drain_end)
                - r.due for r in win]
        first, last = np.median(ttft[:third]), np.median(ttft[-third:])
        row = {"rate_hz": rate, "requests": len(win),
               "waiting_at_close": waiting,
               "ttft_p50_ms": 1e3 * float(np.median(ttft)),
               "ttft_p90_ms": e2e.ttft_p90_ms(rec),
               "itl_p95_ms": e2e.itl_p95_ms(rec),
               "output_tokens_per_s": e2e.output_tokens_per_s(rec),
               "ttft_first_third_ms": 1e3 * float(first),
               "ttft_last_third_ms": 1e3 * float(last),
               "sustained": bool(waiting <= slots and last < 2 * first)}
        print(json.dumps(row), flush=True)
        misses = 0 if row["sustained"] else misses + 1
        if misses == 2:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
