"""Pure-jnp oracles for the Pallas kernels (correctness references)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def int_matmul_ref(x: jnp.ndarray, w: jnp.ndarray,
                   scale: Optional[jnp.ndarray] = None,
                   bias: Optional[jnp.ndarray] = None,
                   acc_bits: int = 32, out_dtype=None) -> jnp.ndarray:
    """Exact integer dot (int32 accumulation, for int8 or int32 operands);
    ``acc_bits`` ≤ 15 stores the raw accumulator as int16, as the kernel
    does (lossless when the SIRA bound holds — tested)."""
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    if scale is None:
        return acc.astype(out_dtype or (jnp.int16 if acc_bits <= 15
                                        else jnp.int32))
    y = acc.astype(jnp.float32) * scale.reshape(1, -1).astype(jnp.float32) \
        + (0.0 if bias is None else bias.reshape(1, -1).astype(jnp.float32))
    return y.astype(out_dtype or jnp.float32)


def multithreshold_ref(x: jnp.ndarray, thresholds: jnp.ndarray,
                       out_bias: int = 0, out_dtype=None) -> jnp.ndarray:
    """x (M, C); thresholds (N, C). out = out_bias + sum_i(x >= T_i).

    out_dtype defaults to the smallest dtype holding [out_bias,
    out_bias + N] (see ``multithreshold.infer_out_dtype``)."""
    from .multithreshold import infer_out_dtype
    if out_dtype is None:
        out_dtype = infer_out_dtype(thresholds.shape[0], out_bias)
    cnt = (x[:, None, :] >= thresholds[None, :, :]).sum(axis=1)
    return (cnt + out_bias).astype(out_dtype)


def multithreshold_searchsorted_ref(x: jnp.ndarray, thresholds: jnp.ndarray,
                                    out_bias: int = 0,
                                    out_dtype=None) -> jnp.ndarray:
    """Bisection formulation (the paper's Fig 17 search tree, as a jnp
    vectorized searchsorted) — same function, O(log N) comparisons."""
    from .multithreshold import infer_out_dtype
    if out_dtype is None:
        out_dtype = infer_out_dtype(thresholds.shape[0], out_bias)
    def per_channel(xc, tc):
        return jnp.searchsorted(tc, xc, side="right")
    cnt = jax.vmap(per_channel, in_axes=(1, 1), out_axes=1)(x, thresholds)
    return (cnt + out_bias).astype(out_dtype)


def quantize_ref(x: jnp.ndarray, scale: jnp.ndarray, zero_point: jnp.ndarray,
                 qmin: int = -128, qmax: int = 127,
                 out_dtype=jnp.int8) -> jnp.ndarray:
    q = jnp.round(x / scale.reshape(1, -1) + zero_point.reshape(1, -1))
    return jnp.clip(q, qmin, qmax).astype(out_dtype)
