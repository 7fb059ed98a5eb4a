"""Run a cell several times, each run its own process, and report each
metric's median and spread (the distance between the first and third
quartiles, by ``statistics.quantiles(values, n=4)``, over the median).

    python3 benchmarks/chip/spread.py --workload qwen2-1.5b.chat \\
        --seconds 51 --seeds 101 102 103 104 105 106 [--trace 0] \\
        [--out runs.jsonl]

This process never touches JAX, so each run holds the chip alone.
"""
import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    results = []
    for seed in args.seeds:
        t = time.perf_counter()
        p = subprocess.run(
            [sys.executable, str(RUN), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds),
             "--trace", str(args.trace)], capture_output=True, text=True)
        line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        res = json.loads(line) if p.returncode == 0 and line else None
        print(f"seed {seed}: rc {p.returncode}", file=sys.stderr)
        print("\n".join(p.stderr.strip().splitlines()[-14:]),
              file=sys.stderr, flush=True)
        row = {"seed": seed, "rc": p.returncode,
               "wall_s": time.perf_counter() - t, "result": res}
        results.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    ok = [r["result"] for r in results if r["result"]]
    summary = {"workload": args.workload, "runs": len(results),
               "ok": len(ok), "correct": sum(r["correct"] for r in ok),
               "metrics": {}}
    for name in sorted({n for r in ok for n in r["metrics"]}):
        v = [r["metrics"][name]["value"] for r in ok
             if name in r["metrics"]]
        summary["metrics"][name] = {
            "median": statistics.median(v), "values": v,
            "spread": spread(v) if len(v) >= 2 else None}
    print(json.dumps(summary), flush=True)
    return 0 if len(ok) == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
