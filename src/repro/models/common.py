"""Shared model building blocks (pure JAX, functional).

Conventions:
  * params are nested dicts of jnp arrays; repeated layers are stacked
    along a leading axis and consumed with jax.lax.scan (keeps the HLO
    O(1) in depth — essential for the 512-device dry-run compiles).
  * ``shard(x, *axes)`` applies a sharding constraint when a mesh is
    active, silently filtering axis names the mesh does not have (so the
    same model code runs on 1-device CPU, the 256-chip pod and the
    512-chip multi-pod mesh).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.quant.quantizer import QuantSpec, compute_scale, fake_quant, \
    fake_quant_dynamic


# --------------------------------------------------------------- sharding

def shard(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint filtered to the current mesh's axes AND to
    divisible dims (a non-divisible constraint makes GSPMD pad the tensor —
    e.g. 2 KV heads padded to a 16-way model axis inflate attention
    buffers 8x; dropping the axis keeps them exact and replicated).

    spec entries: None, an axis name, or a tuple of axis names."""
    m = jax.sharding.get_abstract_mesh()
    if not m.axis_names:
        return x
    axes = set(m.axis_names)
    sizes = dict(zip(m.axis_names, m.axis_sizes))

    def filt(s, dim):
        if s is None:
            return None
        parts = s if isinstance(s, (tuple, list)) else (s,)
        kept = tuple(a for a in parts if a in axes)
        if not kept:
            return None
        total = 1
        for a in kept:
            total *= sizes[a]
        if dim % total != 0:
            return None
        return kept if len(kept) > 1 else kept[0]

    dims = list(x.shape) + [1] * (len(spec) - len(x.shape))
    return jax.lax.with_sharding_constraint(
        x, P(*[filt(s, d) for s, d in zip(spec, dims)]))


BATCH = ("pod", "data")     # data-parallel axes (pod crosses DCN)
MODEL = "model"             # tensor/expert-parallel axis


# ------------------------------------------------------------------ init

def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d),
                                        jnp.float32)).astype(dtype)


# ----------------------------------------------------------------- norms

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ------------------------------------------------------------------ rope

def rope_frequencies(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- linear

def linear(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray] = None,
           quant: Optional[QuantSpec] = None) -> jnp.ndarray:
    """y = x @ w (+ b), optionally fake-quantized (QAT).

    With ``quant``: weights are fake-quantized per output channel and the
    activation per tensor — the standard weight-activation QAT recipe the
    paper's workloads use (§2.1), so the trained model is SIRA-analyzable.
    """
    if isinstance(w, dict):           # packed int8 weights {q, s}
        w = w["q"].astype(x.dtype) * w["s"].astype(x.dtype)
    if quant is not None:
        w_spec = QuantSpec(bits=quant.bits, granularity="per_channel",
                           channel_axis=-1, pot=quant.pot)
        sw, zw = compute_scale(jax.lax.stop_gradient(w), w_spec)
        w = fake_quant(w, sw, zw, w_spec)
        x = fake_quant_dynamic(x, quant)
    y = jnp.einsum("...k,km->...m", x, w)
    if b is not None:
        y = y + b
    return y


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)
