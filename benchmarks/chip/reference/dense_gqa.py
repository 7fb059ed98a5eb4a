"""Plain float32 forward of a dense GQA decoder (Qwen2, Granite 3).

No cache, no kernels, no batching: one sequence, every position, in
``jax.numpy`` at ``Precision.HIGHEST``.  It shares no code with the
program.  It follows the configuration file: the published keys under
``model`` and, where the served model departs from them, the values
under ``served`` (see the file's ``departures``).  Granite's four
multipliers are part of the equations; for Qwen2 they are 1 except the
served embedding multiplier.

    x_0 = m_emb * E[tokens]
    h = RMSNorm(x) ; q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = RoPE(q), RoPE(k)              (rotate-half, base rope_theta)
    a = softmax(m_attn * q k^T + causal) v      (each q head reads the
                                                 k/v head of its group)
    x = x + m_res * a Wo
    x = x + m_res * (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wu)) Wd
    logits = RMSNorm(x) E^T / logits_scaling       (tied embeddings)

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + offset): the weights
store each norm's weight as an offset from 1.

``mode="fp8"`` is the control: the same equations with every matrix
product's operands rounded to float8_e4m3 (per-row scales for
activations, per-column scales for weights), the precision one step
below the configuration's bfloat16.

Computed a layer at a time (a scan over the stacked weights, upcast one
layer at a time) and in blocks of query rows, so it fits on the chip
beside the served model's weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)
#: query rows per attention block
Q_BLOCK = 512


def _round8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, mode):
    """einsum at full float32; in fp8 mode, operands rounded first
    (``a`` per row of its last axis, ``b`` per column)."""
    if mode == "fp8":
        a = _round8(a, axis=-1)
        b = _round8(b, axis=-2)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + offset.astype(jnp.float32))


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, mode):
    """q: (S, H, hd); k, v: (S, KV, hd) -> (S, H * hd), causal."""
    S, H, hd = q.shape
    KV = k.shape[1]
    q = q.reshape(S, KV, H // KV, hd)
    nb = -(-S // Q_BLOCK)
    pad = nb * Q_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, KV, H // KV, hd)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = _mm("qkgd,skd->kgqs", qi, k, mode) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,skd->qkgd", p, v, mode)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, H * hd)[:S]


def _layer(x, w, m, served, mode):
    S = x.shape[0]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // H
    eps = served["rms_norm_eps"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = _norm(x, w["ln1"], eps)
    q, k, v = (_mm("sd,de->se", h, w[n], mode) for n in ("wq", "wk", "wv"))
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(S, H, hd), m["rope_theta"])
    k = _rope(k.reshape(S, KV, hd), m["rope_theta"])
    v = v.reshape(S, KV, hd)
    a = _attention(q, k, v, served["attention_multiplier"], mode)
    x = x + served["residual_multiplier"] * _mm("se,ed->sd", a, w["wo"],
                                                mode)
    h = _norm(x, w["ln2"], eps)
    g = _mm("sd,df->sf", h, w["w_gate"], mode)
    u = _mm("sd,df->sf", h, w["w_up"], mode)
    return x + served["residual_multiplier"] * _mm(
        "sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], mode)


_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
               "w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode"))
def _hidden(w, tokens, *, cfg_key, mode):
    m, served = _CFGS[cfg_key]
    x = w["embed"][tokens].astype(jnp.float32) * served[
        "embedding_multiplier"]
    layers = {n: w[n] for n in _LAYER_KEYS if n in w}

    def body(x, wl):
        return _layer(x, wl, m, served, mode), None

    x, _ = jax.lax.scan(body, x, layers)
    return _norm(x, w["final_norm"], served["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode"))
def _logits(w, h, rows, *, cfg_key, mode):
    m, served = _CFGS[cfg_key]
    e = w["embed"][:m["vocab_size"]].astype(jnp.float32)
    return _mm("sd,vd->sv", h[rows], e, mode) / served["logits_scaling"]


#: configurations seen, by a hashable key (jit's static argument)
_CFGS: Dict[str, Any] = {}


def _key(config) -> str:
    import json
    k = json.dumps([config["model"], config["served"]], sort_keys=True)
    _CFGS[k] = (config["model"], config["served"])
    return k


def bucket(n: int, least: int = 512) -> int:
    """Padded length for ``n`` positions: a power of two, at least
    ``least``, so a run compiles the reference for few shapes."""
    b = least
    while b < n:
        b *= 2
    return b


def logits_at(config, w, tokens, rows, mode: str = "f32"):
    """float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens``: row ``p`` scores the token after position p.

    Padding past the sequence cannot reach earlier positions (causal),
    so the sequence is padded to ``bucket``."""
    tokens = np.asarray(tokens, np.int32)
    toks = np.zeros(bucket(len(tokens)), np.int32)
    toks[:len(tokens)] = tokens
    rows = np.asarray(rows, np.int32)
    idx = np.zeros(bucket(len(rows), 64), np.int32)
    idx[:len(rows)] = rows
    key = _key(config)
    with jax.default_matmul_precision("highest"):
        h = _hidden(w, jnp.asarray(toks), cfg_key=key, mode=mode)
        out = _logits(w, h, jnp.asarray(idx), cfg_key=key, mode=mode)
    return np.asarray(out)[:len(rows)]
