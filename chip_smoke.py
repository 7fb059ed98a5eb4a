#!/usr/bin/env python3
"""Smoke run of both hot paths on a TPU, through their normal entry points.

    python chip_smoke.py             # one chip: QNN executor + serving
    python chip_smoke.py --chips 4   # sharded paged decode vs unsharded

One chip:

* QNN executor: each of the four ``WORKLOADS`` through
  ``build_flow(...).model.compile()`` on a seeded batch of 256.  The
  compiled forward must hold Pallas TPU kernels (``tpu_custom_call``), every
  integer tensor must be bit-exact against ``Graph.execute`` and the float
  outputs within f32 tolerance.
* Serving: qwen2-1.5b at its published widths (bf16, seeded random
  weights) behind ``ServingEngine.generate`` with the SIRA int8 KV cache
  and prefix caching, on 8 greedy requests.  Each request's tokens must
  equal what the same engine gives it served alone, and a paged prefill's
  last-position logits must agree with ``Model.forward``.

``--chips 4`` runs only the sharded paged decode (params and KV pools
over a (data, model) = (2, 2) mesh) and the same requests unsharded on
device 0; the check is token equality.

Every check that fails exits non-zero.  The last line of stdout is one
JSON object naming the device.  Times printed here are smoke timings
from one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

QNN_BATCH = 256
SERVE_ARCH = "qwen2-1.5b"
SERVE_SLOTS, SERVE_MAX_SEQ = 4, 1024
# Paged prefill vs Model.forward, both in bf16 with the same weights: the
# two paths round to bf16 at different points (chunked vs whole-sequence
# projections; a whole-view softmax vs the online-softmax scan), so each
# of the 28 layers may move the residual stream by a few bf16 ulps
# (2^-8 relative).  The bound allows 8 such ulps of the largest logit:
# far below what a wrong mask, position or page mapping gives, which
# moves logits by O(their own size).
PREFILL_REL_BOUND = 8 * 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


# --------------------------------------------------------------- device

def device_phase(n_chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"== device: jax {jax.__version__}, platform={d.platform}, "
          f"kind={d.device_kind!r}, count={len(devs)}", flush=True)
    check(d.platform == "tpu", "JAX found a TPU")
    check(len(devs) >= n_chips, f"at least {n_chips} chip(s)")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


# ---------------------------------------------------------- QNN executor

def _qnn_feeds(model, batch: int, seed: int = 0):
    """Seeded batch drawn from the model's input ranges, in float32 (what
    the compiled forward computes in) and the same values in float64 for
    the numpy interpreter."""
    (inp,) = model.graph.inputs
    shape = (batch,) + tuple(model.metadata["input_shape"][1:])
    r = model.input_ranges[inp]
    lo = np.broadcast_to(np.asarray(r.lo, np.float64), shape)
    hi = np.broadcast_to(np.asarray(r.hi, np.float64), shape)
    x = np.random.default_rng(seed).uniform(lo, hi, size=shape)
    x = x.astype(np.float32)
    return {inp: x}, {inp: x.astype(np.float64)}


def qnn_phase() -> None:
    from repro.core import build_flow
    from repro.core.workloads import WORKLOADS

    for name, make in WORKLOADS.items():
        print(f"== QNN {name} (batch {QNN_BATCH})", flush=True)
        model = build_flow(make()).model
        t0 = time.perf_counter()
        plain = model.compile()
        compiled = model.compile(extra_outputs=plain.int_tensors)
        t_plan = time.perf_counter() - t0
        print(f"  plan: {compiled.kernel_calls}")
        feeds, feeds64 = _qnn_feeds(model, QNN_BATCH)
        hlo = compiled.lowered(feeds).compile().as_text()
        n_kernels = hlo.count("tpu_custom_call")
        check(n_kernels > 0, f"compiled forward holds Pallas TPU kernels "
                             f"({n_kernels} tpu_custom_call)")
        t0 = time.perf_counter()
        got = compiled(feeds)
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = compiled(feeds)
        t_call = time.perf_counter() - t0
        print(f"  smoke timings (not a benchmark): plan {t_plan:.3f} s, "
              f"first call (compile) {t_first:.3f} s, call {t_call:.4f} s")
        want = model.execute(feeds64, record_all=True)
        bad = [t for t in compiled.int_tensors
               if not np.array_equal(got[t].astype(np.float64), want[t])]
        for t in bad:
            diff = got[t].astype(np.float64) != want[t]
            print(f"    {t}: {int(diff.sum())} of {diff.size} differ, first "
                  f"at {tuple(int(i) for i in np.argwhere(diff)[0])}")
        check(not bad, f"{len(compiled.int_tensors)} integer tensors "
                       f"bit-exact vs Graph.execute")
        for o in model.graph.outputs:
            scale = max(float(np.abs(want[o]).max()), 1.0)
            err = float(np.abs(got[o].astype(np.float64) - want[o]).max())
            # the f32 bound of tests/test_backend.py::
            # test_backend_fast_path_matches
            ok = np.allclose(got[o].astype(np.float64), want[o], rtol=1e-5,
                             atol=1e-5 * scale)
            check(ok, f"output {o} within f32 tolerance (max |err| {err!r}, "
                      f"scale {scale!r})")


# -------------------------------------------------------------- serving

def serve_requests(vocab: int, seed: int = 0):
    """8 greedy requests: prompts of 32-384 tokens, two of which share a
    128-token prefix, asking for 16-32 new tokens each."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, size=(128,))
    reqs = []
    for i in range(8):
        n = int(rng.integers(32, 385))
        prompt = rng.integers(0, vocab, size=(n,))
        if i in (1, 5):
            n = max(n, 160)
            prompt = np.concatenate(
                [prefix, rng.integers(0, vocab, size=(n - 128,))])
        reqs.append(Request(prompt=prompt.astype(np.int32),
                            max_new_tokens=int(rng.integers(16, 33)),
                            request_id=i))
    return reqs


def serve_config(**kw):
    from repro.serve import ServingConfig
    return ServingConfig(batch_slots=SERVE_SLOTS, max_seq=SERVE_MAX_SEQ,
                         kv_cache="sira-int8", prefix_cache=True, **kw)


def load_model(cfg):
    from repro.models import get_model
    model = get_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree.leaves(params))
    print(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}, {jnp.dtype(cfg.dtype).name}, {n} params; "
          f"init {time.perf_counter() - t0:.1f} s", flush=True)
    return model, params


def _first_divergence(a, b):
    return next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), None)


def _generate(eng, reqs, label):
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    print(f"  {label}: {sum(map(len, outs))} tokens in "
          f"{time.perf_counter() - t0:.1f} s (smoke timing, compile "
          f"included)", flush=True)
    return outs


def paged_prefill_logits(model, params, spec, prompt, page_size, chunk):
    """Last-position logits of a chunked prefill through
    ``Model.decode_paged`` into a fresh one-slot page pool."""
    from repro.serve import PagedKVCache
    cache = PagedKVCache(model.cfg, spec, 1, SERVE_MAX_SEQ,
                         page_size=page_size)
    assert cache.grow(0, len(prompt))
    step = jax.jit(functools.partial(model.decode_paged, page_size=page_size,
                                     kv_scales=spec.scales()))
    table = cache.slot_table(0)
    logits = None
    for start in range(0, len(prompt), chunk):
        toks = np.zeros((1, chunk), np.int32)
        part = prompt[start:start + chunk]
        toks[0, :len(part)] = part
        logits, cache.pages = step(params, jnp.asarray(toks), cache.pages,
                                   table, jnp.full((1,), start, jnp.int32))
    return np.asarray(logits[0, (len(prompt) - 1) % chunk], np.float32)


def _vocab_logits(cfg, logits):
    """Drop the padded vocab entries, which the model masks to -1e30."""
    return np.asarray(logits, np.float32)[..., :cfg.vocab]


def serving_phase(cfg) -> None:
    from repro.serve import ServingEngine

    print(f"== serving {cfg.name}", flush=True)
    model, params = load_model(cfg)
    t0 = time.perf_counter()
    eng = ServingEngine(model, params, serve_config())
    spec = eng.kv_spec
    print(f"  SIRA int8 KV layers: {spec.n_int8} of {cfg.n_layers} "
          f"(derive {time.perf_counter() - t0:.1f} s)")
    fp = [l.reason for l in spec.layers if not l.int8]
    if fp:
        print(f"  fp fallback, layer 0: {fp[0]}")
    reqs = serve_requests(cfg.vocab)
    outs = _generate(eng, reqs, "8 requests packed into 4 slots")
    print(f"  metrics: {eng.metrics.summary()}")
    check([len(o) for o in outs] == [r.max_new_tokens for r in reqs],
          "every request got its max_new_tokens")
    check(eng.metrics.prefix_hit_rate > 0, "the shared prefix was reused")
    eng.reset_metrics()
    for i, (r, out) in enumerate(zip(reqs, outs)):
        solo = eng.generate([r])[0]
        first = _first_divergence(out, solo)
        check(solo == out, f"request {i} (prompt {len(r.prompt)}): packed "
                           f"== solo" + ("" if first is None else
                                         f", first diverges at {first}"))

    prompt = reqs[0].prompt
    t0 = time.perf_counter()
    want = _vocab_logits(cfg, jax.jit(model.forward)(
        params, jnp.asarray(prompt)[None])[0, -1])
    got = _vocab_logits(cfg, paged_prefill_logits(
        model, params, spec, prompt, eng.config.page_size,
        eng.config.prefill_chunk))
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    print(f"  paged prefill vs forward ({len(prompt)} tokens, "
          f"{time.perf_counter() - t0:.1f} s): max |err| {err!r}, max "
          f"|logit| {scale!r}, argmax {int(got.argmax())} vs "
          f"{int(want.argmax())}")
    check(err <= PREFILL_REL_BOUND * scale,
          f"paged prefill logits within {PREFILL_REL_BOUND!r} x max |logit| "
          f"of Model.forward")


def sharded_phase(cfg, n_chips: int) -> None:
    from repro.launch.mesh import make_debug_mesh
    from repro.serve import ServingEngine

    print(f"== sharded paged decode, {cfg.name} on {n_chips} chips",
          flush=True)
    model, params = load_model(cfg)
    mesh = make_debug_mesh(n_chips)
    print(f"  mesh {dict(mesh.shape)}")
    check(mesh.devices.size == n_chips, f"mesh spans {n_chips} devices")
    reqs = serve_requests(cfg.vocab)
    sharded = ServingEngine(model, params, serve_config(mesh=mesh))
    pool = sharded.cache.pages[0]["k"]
    held = {s.device for s in pool.addressable_shards}
    print(f"  KV pool {pool.shape} sharding {pool.sharding.spec}, shard "
          f"{pool.addressable_shards[0].data.shape} on {len(held)} devices")
    check(len(held) == n_chips, "KV pools live on every chip")
    got = _generate(sharded, reqs, "sharded")
    plain = ServingEngine(model, params, serve_config())
    want = _generate(plain, reqs, "unsharded, device 0")
    for i, (a, b) in enumerate(zip(got, want)):
        first = _first_divergence(a, b)
        check(a == b, f"request {i}: sharded == unsharded" +
              ("" if first is None else f", first diverges at {first}"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded paged decode and its "
                         "unsharded comparison")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")
    from repro.configs import get_config

    cfg = get_config(SERVE_ARCH)
    try:
        device = device_phase(args.chips)
        if args.chips == 1:
            qnn_phase()
            serving_phase(cfg)
        else:
            sharded_phase(cfg, args.chips)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: check failed: {e}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
