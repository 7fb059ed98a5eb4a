import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init).  Do not move them.

__doc__ = """Multi-pod dry-run driver.

For every (architecture × input shape × mesh) cell:
  * build the step function (train_step / prefill forward / decode_step),
  * jit with explicit in/out shardings over the production mesh,
  * ``.lower(**ShapeDtypeStruct specs).compile()``,
  * record memory_analysis / cost_analysis / collective schedule →
    experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --mesh pod          # single cell, 256-chip mesh
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (SHAPES, cell_applicable, get_config,
                           input_specs, list_archs)
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (batch_pspec, filter_pspec_for_mesh,
                                    named, opt_pspecs, param_pspecs)
from repro.models import get_model
from repro.optim.adamw import AdamW, AdamWState
from repro.quant.quantizer import QuantSpec
from repro.roofline.analysis import model_flops_for, roofline_from
from repro.roofline.hlo_cost import analyze_hlo
from repro.train.train_step import TrainState, init_train_state, \
    make_train_step

BATCH = ("pod", "data")


# ------------------------------------------------------------- cache specs

def cache_pspecs(cache: Any, kv_heads: int = 0,
                 model_size: int = 16) -> Any:
    """PartitionSpecs for decode caches, assigned by leaf key name.

    KV caches shard their head axis on "model" when divisible; otherwise
    the *sequence* axis is model-sharded (flash-decode style: GSPMD adds
    the partial-softmax all-reduce), which keeps the cache 16-way sharded
    for the GQA archs with 2-8 KV heads instead of replicating it."""
    def walk(tree, key):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        nd = len(tree.shape)
        if key in ("k", "v"):        # (n..., B, S, KV, hd)
            pad = (None,) * (nd - 4)
            if kv_heads and kv_heads % model_size == 0:
                return P(*pad, BATCH, None, "model", None)
            return P(*pad, BATCH, "model", None, None)
        if key == "conv":            # (n..., B, K-1, ch)
            pad = (None,) * (nd - 3)
            return P(*pad, BATCH, None, "model")
        if key == "ssd":             # (n..., B, H, P, N)
            pad = (None,) * (nd - 4)
            return P(*pad, BATCH, "model", None, None)
        return P(*((None,) * nd))
    return walk(cache, "")


def state_pspecs(state_shapes: TrainState, pspecs_params) -> TrainState:
    op = opt_pspecs(state_shapes.opt.master, pspecs_params)
    return TrainState(
        params=pspecs_params,
        opt=AdamWState(step=P(), master=op,
                       m=jax.tree.map(lambda s: s, op),
                       v=jax.tree.map(lambda s: s, op)),
        error_feedback=None,
        rng=P())


# --------------------------------------------------------------- one cell

@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    skipped: bool = False
    reason: str = ""
    compile_s: float = 0.0
    memory: Dict[str, float] = dataclasses.field(default_factory=dict)
    cost: Dict[str, float] = dataclasses.field(default_factory=dict)
    collectives: Dict[str, Any] = dataclasses.field(default_factory=dict)
    roofline: Dict[str, float] = dataclasses.field(default_factory=dict)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 8, quant_bits: int = 0,
             save_hlo: Optional[str] = None,
             zero2: bool = False, remat: bool = True,
             int8_weights: bool = False,
             int8_kv: bool = False,
             capacity_factor: float = 0.0) -> CellResult:
    cfg = get_config(arch)
    if capacity_factor and cfg.moe.n_experts:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, moe=_dc.replace(
            cfg.moe, capacity_factor=capacity_factor))
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ok, reason = cell_applicable(cfg, shape)
    if not ok:
        return CellResult(arch, shape_name, mesh_name, ok=False,
                          skipped=True, reason=reason)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    model = get_model(cfg)
    quant = QuantSpec(bits=quant_bits) if quant_bits else None
    specs = input_specs(cfg, shape)

    def make_params_shapes():
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if int8_weights:
            from repro.quant.quantizer import pack_weights_int8
            shapes = jax.eval_shape(pack_weights_int8, shapes)
        return shapes

    pspecs = param_pspecs(make_params_shapes())

    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                optimizer = AdamW(total_steps=1000)
                step_fn = make_train_step(model, optimizer,
                                          microbatches=microbatches,
                                          quant=quant, remat=remat)
                state_shapes = jax.eval_shape(
                    lambda k: init_train_state(model, optimizer, k),
                    jax.random.PRNGKey(0))
                sspec = state_pspecs(state_shapes, pspecs)
                if zero2:
                    pass  # grads constrained inside train_step via flag
                bspec = {k: batch_pspec(len(v.shape))
                         for k, v in specs.items()}
                lowered = jax.jit(
                    step_fn,
                    in_shardings=(named(mesh, sspec, state_shapes),
                                  named(mesh, bspec, specs)),
                    out_shardings=(named(mesh, sspec, state_shapes), None),
                    donate_argnums=(0,),
                ).lower(state_shapes, specs)
            elif shape.kind == "prefill":
                def fwd(params, batch):
                    return model.forward(params, batch["tokens"],
                                         batch.get("frontend_embed"),
                                         quant=quant, remat=remat)
                params_shapes = make_params_shapes()
                bspec = {k: batch_pspec(len(v.shape))
                         for k, v in specs.items()}
                s_total = shape.seq_len + (
                    specs["frontend_embed"].shape[1]
                    if "frontend_embed" in specs else 0)
                logits_shape = (shape.global_batch, s_total,
                                cfg.vocab_padded)
                out_spec = NamedSharding(
                    mesh, filter_pspec_for_mesh(P(BATCH, None, "model"),
                                                mesh, logits_shape))
                lowered = jax.jit(
                    fwd,
                    in_shardings=(named(mesh, pspecs, params_shapes),
                                  named(mesh, bspec, specs)),
                    out_shardings=out_spec,
                ).lower(params_shapes, specs)
            else:  # decode
                params_shapes = make_params_shapes()
                cache_shapes = jax.eval_shape(
                    lambda: model.init_cache(
                        shape.global_batch, shape.seq_len,
                        kv_dtype=jnp.int8 if int8_kv else None))
                cspec = cache_pspecs(cache_shapes, cfg.n_kv_heads,
                                     mesh.devices.shape[-1])

                def dec(params, tokens, cache, idx):
                    return model.decode_step(params, tokens, cache, idx,
                                             quant=quant)
                logits_shape = (shape.global_batch, 1,
                                cfg.vocab_padded)
                out_spec = (NamedSharding(mesh, filter_pspec_for_mesh(
                    P(BATCH, None, "model"), mesh, logits_shape)),
                    named(mesh, cspec, cache_shapes))
                lowered = jax.jit(
                    dec,
                    in_shardings=(named(mesh, pspecs, params_shapes),
                                  NamedSharding(mesh, filter_pspec_for_mesh(
                                      P(BATCH, None), mesh,
                                      specs["tokens"].shape)),
                                  named(mesh, cspec, cache_shapes),
                                  NamedSharding(mesh, P())),
                    out_shardings=out_spec,
                    donate_argnums=(2,),
                ).lower(params_shapes, specs["tokens"], cache_shapes,
                        specs["cache_index"])
            compiled = lowered.compile()
    except Exception:
        return CellResult(arch, shape_name, mesh_name, ok=False,
                          reason=traceback.format_exc()[-2000:],
                          compile_s=time.time() - t0)
    compile_s = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[f] = float(getattr(ma, f))
        mem["total_per_device_gb"] = (
            mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
            + mem["temp_size_in_bytes"] - mem["alias_size_in_bytes"]) / 2**30
    except Exception as e:  # pragma: no cover
        mem["error"] = str(e)

    cost_xla = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    pod_group = 2 if multi_pod else None
    # trip-count-aware totals (XLA cost_analysis counts scan bodies once)
    totals = analyze_hlo(hlo, pod_group_size=pod_group)
    cost = {"flops": totals.flops, "bytes accessed": totals.bytes,
            "xla_flops_1trip": float(cost_xla.get("flops", 0.0)),
            "xla_bytes_1trip": float(cost_xla.get("bytes accessed", 0.0))}
    from repro.roofline.analysis import CollectiveStats
    colls = CollectiveStats(
        counts={k: int(v) for k, v in totals.collective_counts.items()},
        operand_bytes={k: int(v)
                       for k, v in totals.collective_bytes.items()},
        wire_bytes={k: int(v) for k, v in totals.collective_bytes.items()},
        cross_pod_bytes=int(totals.cross_pod_bytes))
    mf = model_flops_for(cfg, shape, shape.kind)
    rl = roofline_from(cost, colls, n_chips, mf)

    return CellResult(
        arch, shape_name, mesh_name, ok=True, compile_s=compile_s,
        memory=mem,
        cost=cost,
        collectives=dict(counts=colls.counts,
                         operand_bytes=colls.operand_bytes,
                         wire_bytes=colls.wire_bytes,
                         cross_pod_bytes=colls.cross_pod_bytes),
        roofline=rl.to_dict())


# -------------------------------------------------------------------- CLI

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--quant-bits", type=int, default=0)
    ap.add_argument("--int8-weights", action="store_true")
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--attn-p-bf16", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                if args.attn_p_bf16:
                    import repro.models.attention as attn_mod
                    attn_mod.P_DTYPE = jnp.bfloat16
                res = run_cell(arch, shape, mp,
                               microbatches=args.microbatches,
                               quant_bits=args.quant_bits,
                               remat=not args.no_remat,
                               int8_weights=args.int8_weights,
                               int8_kv=args.int8_kv,
                               capacity_factor=args.capacity_factor,
                               save_hlo=args.save_hlo)
                results.append(res)
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(dataclasses.asdict(res), f, indent=1)
                status = ("SKIP" if res.skipped else
                          "OK" if res.ok else "FAIL")
                rl = res.roofline
                extra = ""
                if res.ok:
                    extra = (f" compile={res.compile_s:.0f}s "
                             f"mem={res.memory.get('total_per_device_gb', -1):.2f}GB "
                             f"bottleneck={rl['bottleneck']}")
                print(f"[{status}] {tag}{extra}", flush=True)
                if not res.ok and not res.skipped:
                    print(res.reason[-600:], flush=True)
    n_ok = sum(r.ok for r in results)
    n_skip = sum(r.skipped for r in results)
    print(f"\n{n_ok} ok, {n_skip} skipped, "
          f"{len(results) - n_ok - n_skip} failed / {len(results)} cells")


if __name__ == "__main__":
    main()
