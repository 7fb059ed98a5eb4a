"""Readings that set a cell's limit on the widest logit gap.

For each seed, in one process (set-up is long): the cell's own window at
its own load for ``--seconds``, the drain, and the sample a run draws;
then, over that sample, the program's widest gap (the lower reading,
from sound runs) and the float8 control's (the upper reading).  Prints
one JSON line per seed and a summary.

    python3 benchmarks/chip/calibrate.py --workload granite-3-2b.longctx \\
        --seconds 20 --seeds 11 12 13 ... [--control-seeds 3]

The limit is then set between the largest program reading and the
smallest control reading (``cells/<cell>.json``).
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float, control: bool):
    """One seed's window, drain and sample; the program's widest gap
    and, with ``control``, the float8 control's."""
    from benchmarks.chip import check, harness

    ses = harness.setup(cell, seed=seed, seconds=seconds, trace=False)
    rec = harness.measure(ses, seconds=seconds, trace=False,
                          process_start=PROCESS_START)
    harness.release(ses)
    picked = check.sample(rec.requests, seed)
    prog = [float(check.served_gaps(cell, ses.w, r).max())
            for r in picked]
    row = {"seed": seed, "requests": len(picked),
           "tokens": sum(len(r.tokens) for r in picked),
           "failed": sum(r.in_window and not r.finished
                         for r in rec.requests),
           "program_max_gap": max(prog, default=None),
           "program_gaps": prog}
    if control:
        ctl = [float(check.control_gaps(cell, ses.w, r).max())
               for r in picked]
        row.update(control_max_gap=max(ctl, default=None),
                   control_gaps=ctl)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first N seeds")
    args = ap.parse_args(argv)
    from benchmarks.chip import spec
    from benchmarks.chip.run import chip_problem

    cell = spec.load_cell(args.workload)
    problem = chip_problem(cell.chips)
    if problem:
        print(f"calibrate.py: {problem}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for i, seed in enumerate(args.seeds):
        rows.append(readings(cell, seed, args.seconds,
                             control=i < args.control_seeds))
        print(json.dumps(rows[-1]), flush=True)
        gc.collect()         # this seed's weights go before the next's
    lower = max(r["program_max_gap"] for r in rows)
    upper = min((r["control_max_gap"] for r in rows
                 if "control_max_gap" in r), default=None)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
