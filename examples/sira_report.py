"""SIRA analysis report: accumulator widths, layer-tail implementation
choice, and FPGA/TPU cost projections — driven by the SiraModel pass
pipeline.  With ``--workload``, additionally runs the dataflow DSE
subsystem and prints the per-node resource/II/style report plus the
SIRA-vs-baseline accelerator deltas and the folding search.

    PYTHONPATH=src python examples/sira_report.py --arch glm4-9b
    PYTHONPATH=src python examples/sira_report.py --workload TFC-w2a2

Observability hooks (``repro.obs``): ``--trace out.json`` records the
whole report run (flow steps, range analysis, compile) as a Chrome
trace_event JSON loadable in Perfetto; ``--explain TENSOR`` prints the
range-provenance chain for a tensor of the analyzed model — which op
handler produced each range, under which abstract domain, and which
input was the widening culprit.

    PYTHONPATH=src python examples/sira_report.py --workload CNV-w2a2 \
        --trace out.json --explain <acc-tensor>
"""
import argparse


from repro.configs import get_config, list_archs
from repro.core import (MinimizeAccumulators, SiraModel, Streamline,
                        build_flow, summarize)
from repro.core.workloads import ALL_WORKLOADS
from repro.dataflow import (compare_sira_vs_baseline, extract_dataflow,
                            search_folding, select_tail_style, tail_cost)
from repro.models.export import export_block_graph
from repro.obs import disable_tracing, enable_tracing


def arch_report(args) -> "SiraModel":
    cfg = get_config(args.arch, reduced=True)
    print(f"=== SIRA report: {args.arch} (reduced block, "
          f"w{args.w_bits}a{args.a_bits}) ===")
    g, inp = export_block_graph(cfg, w_bits=args.w_bits, a_bits=args.a_bits)
    model = SiraModel(g, inp, name=args.arch).transform(
        Streamline(), MinimizeAccumulators())
    reps = model.metadata["accumulator_reports"]
    print(f"{'kernel':28s} {'K':>6s} {'SIRA':>5s} {'dtype':>6s} {'save':>6s}")
    for r in reps:
        print(f"{r.node_name:28s} {r.K:6d} {r.sira_bits:4d}b "
              f"{r.datatype_bits:5d}b {r.reduction_vs_datatype:6.0%}")
    s = summarize(reps)
    print(f"\nmean accumulator: {s['mean_sira']:.1f}b SIRA vs "
          f"{s['mean_datatype']:.1f}b datatype-bound "
          f"({s['reduction_vs_datatype']:.0%} smaller; paper avg 22%)")

    n_i = int(round(s["mean_sira"]))
    style = select_tail_style(n_i, args.a_bits, 16, cfg.d_model, 4)
    tc = tail_cost(n_i, args.a_bits, 16, cfg.d_model, 4)
    print(f"\nlayer-tail style for {args.a_bits}-bit activations: {style}")
    print(f"  thresholding: {tc.thresholding_luts:,.0f} LUTs | "
          f"composite fixed16.8: {tc.composite_luts:,.0f} LUTs")
    print("TPU mapping: int32 MXU accumulation, stored as "
          f"{'int16' if s['mean_sira'] <= 15 else 'int32'}, fused "
          f"multithreshold tail (1 HBM pass)")
    return model


def verification_report(model) -> None:
    """Surface the verify_ranges containment/coverage artifacts
    (``--verify``): violations, per-tensor range coverage, and channels
    SIRA proves stuck at a constant value."""
    from repro.core import stuck_channels
    rep = model.metadata.get("verification")
    if rep is None:
        print("\nverification: no report (no sample inputs available)")
        return
    print(f"\n=== range verification ({model.domain} domain) ===")
    status = "PASS" if rep.contained else "FAIL"
    print(f"containment: {status} "
          f"({len(rep.observed)} tensors instrumented)")
    for v in rep.violations[:10]:
        print(f"  violation: {v}")
    cov = sorted(rep.coverage.items(), key=lambda kv: kv[1])
    if cov:
        mean_cov = sum(c for _, c in cov) / len(cov)
        print(f"coverage: mean {mean_cov:.0%} of proven width observed; "
              f"loosest tensors:")
        for name, c in cov[:5]:
            lo, hi = rep.observed[name]
            print(f"  {name:28s} {c:6.1%}  observed [{lo:.4g}, {hi:.4g}]")
    n_stuck = 0
    for t in model.graph.outputs:
        if t in model.ranges:
            mask = stuck_channels(model.ranges, t)
            n_stuck += int(mask.sum())
    if n_stuck:
        print(f"stuck output channels (provably constant): {n_stuck}")


def workload_report(args) -> "SiraModel":
    print(f"=== Dataflow DSE report: {args.workload} on {args.device} "
          f"[{args.domain} domain] ===")
    model = build_flow(ALL_WORKLOADS[args.workload](),
                       domain=args.domain).model

    reports = model.metadata.get("tail_reports", [])
    if reports:
        print("\nthreshold conversion (monotonicity certificates):")
        for r in reports:
            if r.converted:
                print(f"  {r.anchor:14s} converted  {r.status}/{r.method} "
                      f"({r.n_ops} ops -> 1 MultiThreshold)")
            else:
                print(f"  {r.anchor:14s} kept chain uncertified: "
                      f"{r.reason} -> meta-kernel pricing")

    dfg = extract_dataflow(model)
    fold = search_folding(model, target_fps=args.target_fps,
                          device=args.device, dataflow_graph=dfg)
    folding = fold.folding if fold.feasible else None
    comp = compare_sira_vs_baseline(model, device=args.device,
                                    folding=folding, dataflow_graph=dfg)
    est = comp.sira

    print(f"\n{'node':22s} {'kind':11s} {'style':13s} {'PExSIMD':>8s} "
          f"{'II':>7s} {'bits i/o/acc':>12s} {'LUT':>7s} {'DSP':>4s} "
          f"{'BRAM':>5s}")
    for n in est.nodes:
        mark = " <- bottleneck" if n.name == est.bottleneck else ""
        print(f"{n.name:22s} {n.kind:11s} {n.style:13s} "
              f"{n.pe:>4d}x{n.simd:<3d} {n.cycles:>7d} "
              f"{n.in_bits:>4d}/{n.out_bits}/{n.acc_bits:<3d} "
              f"{n.luts:>7.0f} {n.dsps:>4d} {n.brams:>5d}{mark}")
    fifo_luts = sum(f.luts for f in est.fifos)
    fifo_brams = sum(f.brams for f in est.fifos)
    print(f"{'(stream FIFOs)':22s} {'':11s} {'':13s} {'':>8s} {'':>7s} "
          f"{'':>12s} {fifo_luts:>7.0f} {'':>4s} {fifo_brams:>5d}")

    b = comp.baseline
    print("\ntotals (SIRA vs datatype-bound baseline, same folding):")
    print(f"  LUTs {b.luts:,.0f} -> {est.luts:,.0f} "
          f"(-{comp.lut_reduction:.0%}; paper -17%)")
    print(f"  DSPs {b.dsps} -> {est.dsps} "
          f"(-{comp.dsp_reduction:.0%}; paper -66%)")
    print(f"  BRAMs {b.brams} -> {est.brams}")
    print(f"  mean accumulator {comp.mean_acc_bits_datatype:.1f}b -> "
          f"{comp.mean_acc_bits_sira:.1f}b "
          f"(-{comp.acc_bits_reduction:.0%}; paper -22%)")
    print(f"  layer-tail rLUT {comp.tail_lut_ratio:.2f}")

    print(f"\nfolding search @ {args.target_fps:g} FPS on {args.device}:")
    if fold.feasible:
        util = ", ".join(f"{k} {v:.0%}"
                         for k, v in fold.utilization.items())
        print(f"  feasible — achieved {fold.achieved_fps:,.0f} FPS "
              f"({util})")
    else:
        print(f"  infeasible — binding constraint: {fold.binding}")

    if args.verify:
        verification_report(model)
    return model


def explain_report(model, tensor: str) -> None:
    """Print the range-provenance chain for one tensor (``--explain``)."""
    print(f"\n=== range provenance: {tensor} ===")
    try:
        chain = model.explain(tensor)
    except KeyError as e:
        raise SystemExit(f"--explain: {e.args[0]}") from None
    print(chain.render())


def _resolve_workload(name: str) -> str:
    """Accept either the exact workload key or a unique prefix
    (``CNV`` -> ``CNV-w2a2``)."""
    if name in ALL_WORKLOADS:
        return name
    hits = sorted(k for k in ALL_WORKLOADS if k.startswith(name))
    if len(hits) == 1:
        return hits[0]
    raise SystemExit(f"--workload: unknown workload {name!r} "
                     f"(choices: {sorted(ALL_WORKLOADS)})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list_archs())
    ap.add_argument("--workload", metavar="NAME",
                    help="print the dataflow DSE per-node report for a "
                         "QNN workload instead of an LM-arch report "
                         f"(choices: {sorted(ALL_WORKLOADS)}; a unique "
                         "prefix like 'CNV' is accepted)")
    ap.add_argument("--device", default="pynq-z1")
    ap.add_argument("--target-fps", type=float, default=1000.0)
    ap.add_argument("--w-bits", type=int, default=4)
    ap.add_argument("--a-bits", type=int, default=4)
    ap.add_argument("--domain", default="interval",
                    choices=("interval", "affine"),
                    help="range-analysis abstract domain (affine = "
                         "zonotope reduced product, tighter bounds)")
    ap.add_argument("--verify", action="store_true",
                    help="print the verify_ranges containment/coverage "
                         "report (workload reports only)")
    ap.add_argument("--trace", default=None, metavar="TRACE_JSON",
                    help="record the report run (flow/analysis/compile "
                         "spans) and write a Chrome trace_event JSON "
                         "loadable in Perfetto")
    ap.add_argument("--explain", default=None, metavar="TENSOR",
                    help="print the range-provenance chain for TENSOR "
                         "of the analyzed model")
    args = ap.parse_args()
    if args.workload:
        args.workload = _resolve_workload(args.workload)

    tracer = enable_tracing() if args.trace else None
    try:
        model = workload_report(args) if args.workload else arch_report(args)
        if args.trace:
            # compile too, so the trace carries the backend-lowering
            # spans alongside flow/analysis — the full pipeline picture
            model.compile()
        if args.explain:
            explain_report(model, args.explain)
    finally:
        if tracer is not None:
            disable_tracing()
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        print(f"\nwrote {args.trace} ({len(tracer.spans)} spans — open "
              f"in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
