"""Accumulator-budget sweep: the end-to-end train -> SIRA -> DSE chain.

For each accumulator budget B (0 = unconstrained, then tightening): run
accumulator-aware QAT (`repro.qat`), export the trained weights to a
SiraModel, run the default build flow, and report the SIRA-*proven*
accumulator bits, the task loss, and the unfolded LUT/DSP estimates from
the dataflow DSE — the paper-stack's "training knob -> proven bits ->
resources" curve.

Two invariants are asserted in-bench and again as hard floors in
``scripts/check_bench.py``:

  * ``proven_bits <= budget`` on every constrained layer (the A2Q
    guarantee, a theorem given the toz quantizer + frozen scales — any
    violation is a soundness bug, not noise);
  * SIRA LUT/DSP estimates are monotone non-increasing as the budget
    tightens (``luts_le_prev`` / ``dsps_le_prev``).

    PYTHONPATH=src python benchmarks/bench_qat.py \
        [--quick] [--budgets 0,14,12,10] [--out BENCH_qat.json]
"""
from __future__ import annotations

import argparse
import time


def bench_budget(budget: int, prev: dict, args) -> dict:
    from repro.dataflow import compare_sira_vs_baseline
    from repro.qat import (QATConfig, check_budget_invariant,
                           proven_layer_bits, run_qat)

    t0 = time.perf_counter()
    cfg = QATConfig(in_dim=args.in_dim,
                    hidden=tuple(args.hidden),
                    classes=args.classes,
                    weight_bits=args.weight_bits,
                    act_bits=args.act_bits,
                    budget=budget,
                    zero_center=args.zero_center,
                    steps=args.steps,
                    seed=args.seed)
    res = run_qat(cfg)
    result, bits = proven_layer_bits(
        res.model, res.state.params, name=f"qat-b{budget}")
    if budget:
        check_budget_invariant(res.model, res.state.params, bits)
    comp = compare_sira_vs_baseline(result.model, device=args.device)

    row = dict(
        budget=budget,
        constrained_layers=len(bits) if budget else 0,
        proven_bits=bits,
        proven_bits_max=max(bits),
        proven_bits_sum=sum(bits),
        task_loss=round(res.final_loss, 4),
        sira_luts=round(comp.sira.luts, 1),
        sira_dsps=comp.sira.dsps,
        baseline_luts=round(comp.baseline.luts, 1),
        baseline_dsps=comp.baseline.dsps,
        seconds=time.perf_counter() - t0,
    )
    if budget:
        # the A2Q guarantee as a number: min over layers of
        # (budget - proven bits); the gate holds it >= 0 as a hard floor
        row["budget_headroom"] = budget - row["proven_bits_max"]
    if prev:
        # budgets sweep loosest-first, so resources may only shrink
        row["luts_le_prev"] = bool(row["sira_luts"]
                                   <= prev["sira_luts"] + 1e-9)
        row["dsps_le_prev"] = bool(row["sira_dsps"] <= prev["sira_dsps"])
        assert row["luts_le_prev"] and row["dsps_le_prev"], (
            f"budget {budget}: DSE resources grew vs looser budget "
            f"{prev['budget']} ({prev['sira_luts']}->{row['sira_luts']} "
            f"LUTs, {prev['sira_dsps']}->{row['sira_dsps']} DSPs)")
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budgets", default="0,14,12,10",
                    help="comma list, loosest first; 0 = unconstrained")
    ap.add_argument("--in-dim", type=int, default=16)
    ap.add_argument("--hidden", type=int, nargs="+", default=[32, 32])
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--weight-bits", type=int, default=4)
    ap.add_argument("--act-bits", type=int, default=4)
    ap.add_argument("--zero-center", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--device", default="pynq-z1")
    ap.add_argument("--quick", action="store_true",
                    help="shorter training (tier1/CI gating mode — the "
                         "committed baseline is generated from this)")
    ap.add_argument("--out", default="BENCH_qat.json")
    args = ap.parse_args()
    if args.quick:
        args.steps = min(args.steps, 80)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    budgets = [int(b) for b in args.budgets.split(",")]
    results, prev = [], {}
    for budget in budgets:
        row = bench_budget(budget, prev, args)
        results.append(row)
        prev = row
        print(f"budget {budget or '-':>3}: proven {row['proven_bits']} "
              f"(max {row['proven_bits_max']})  "
              f"loss {row['task_loss']:.3f}  "
              f"LUT {row['sira_luts']:.0f}  DSP {row['sira_dsps']}",
              flush=True)

    payload = dict(arch=f"mlp{args.in_dim}-"
                        f"{'x'.join(map(str, args.hidden))}-{args.classes}",
                   weight_bits=args.weight_bits, act_bits=args.act_bits,
                   zero_center=args.zero_center, steps=args.steps,
                   seed=args.seed, device=args.device, results=results)
    from repro.obs.metrics import export_bench
    export_bench(payload, args.out, key=("budget",))
    print(f"wrote {args.out} (+ Prometheus text next to it)")


if __name__ == "__main__":
    main()
