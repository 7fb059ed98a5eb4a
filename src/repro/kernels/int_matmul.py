"""Pallas TPU kernel: integer matmul with SIRA-minimized accumulation and a
fused scale/bias dequantization epilogue.

This is the MXU realization of the paper's streamlined integer MatMul
(§4.1.2) + accumulator minimization (§4.2):

  * inputs are int8 (the revealed integer kernel), multiplied on the MXU's
    native 8-bit path and always accumulated in int32 (Mosaic accepts no
    narrower MXU accumulator on TPU v5e);
  * the SIRA bound selects the *output storage* dtype of the raw
    accumulator: int16 when the lossless width ≤ 15 bits (halving the
    bytes written to HBM), else int32 — a cast in the epilogue, exact
    whenever the bound holds;
  * the single aggregated scale/bias (the whole layer tail after
    aggregation) is applied as a fused epilogue on the final K step —
    exactly one HBM pass for matmul + tail.

Block sizes default to MXU-aligned (128×128×128) tiles, double-buffered by
the Pallas pipeline across the K grid axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
                   k_steps: int, out_dtype, dequant: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if dequant:
            s = scale_ref[...]            # (1, bn)
            b = bias_ref[...]             # (1, bn)
            o_ref[...] = (acc.astype(jnp.float32) * s + b).astype(out_dtype)
        else:
            o_ref[...] = acc.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "acc_bits",
                                             "out_dtype", "interpret"))
def int_matmul(x: jnp.ndarray, w: jnp.ndarray,
               scale: Optional[jnp.ndarray] = None,
               bias: Optional[jnp.ndarray] = None,
               *, bm: int = 128, bn: int = 128, bk: int = 128,
               acc_bits: int = 32, out_dtype=None,
               interpret: bool = False) -> jnp.ndarray:
    """x (M, K) int8 @ w (K, N) int8 → int accumulate → optional dequant.

    acc_bits: SIRA-minimized accumulator width; ≤15 stores the raw
        accumulator as int16 (accumulation is int32 either way).
    scale/bias: per-output-channel (N,) aggregated layer-tail parameters;
    if given, output is float32 (dequantized), else the raw accumulator.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"shape ({M},{K},{N}) not divisible by block ({bm},{bk},{bn})"
    dequant = scale is not None
    if out_dtype is None:
        out_dtype = (jnp.float32 if dequant
                     else jnp.int16 if acc_bits <= 15 else jnp.int32)
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    scale2 = scale.reshape(1, N).astype(jnp.float32)
    bias2 = bias.reshape(1, N).astype(jnp.float32)

    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)
    kernel = functools.partial(_matmul_kernel, k_steps=k_steps,
                               out_dtype=out_dtype, dequant=dequant)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w, scale2, bias2)
