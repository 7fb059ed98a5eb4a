"""Architecture kind ``dense_gqa``: a dense decoder with grouped-query
attention in every layer and a SwiGLU MLP (Qwen2, Granite 3).

A configuration names its kind (``"kind"`` in ``configs/<name>.json``)
and the harness finds this file by that name (``spec.arch_kind``).  A
kind gives, each from the whole configuration file:

- ``arch_config(config)``: the program's ``ArchConfig``;
- ``shapes(config, vocab_rows)`` and ``std(name, shape)``: the seeded
  weights' names, shapes and spreads (``weights.make`` draws them);
- ``to_program(w, model)``: the program's parameter tree over them;
- ``logits_at(config, w, seq, rows, mode)``: the plain reference;
- ``token_flops``, ``prefill_flops``, ``decode_step_bytes``: the work
  the algorithm needs.

Operations and bytes come from the published sizes and each token's
real context length.  Nothing here depends on how the program does the
work: a token at position ``p`` (0-based) attends to the ``p + 1`` keys
up to and including its own, whatever the cache holds or gathers;
logits are needed only where a token is sampled (the last prompt
position and every decoded token); a prefix that an earlier request
computed is not computed again.  One multiply-add counts as two
operations; only matrix products and attention are counted (norms,
RoPE, biases and the softmax are a rounding error beside them).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable

from benchmarks.chip.reference import dense_gqa as reference

#: Random weights that make a model worth checking.  With the embedding
#: as wide as the blocks' outputs the residual stream is the current
#: token's own embedding, and every prediction is to repeat it, whatever
#: attention computes: so the embedding's std is a quarter of a
#: projection's (``EMBED_SCALE`` x hidden**-0.5).  Queries and keys are
#: twice a projection's (``QK_SCALE``), so that attention scores spread
#: over some units and the history decides what comes next.
EMBED_SCALE = 0.25
QK_SCALE = 2.0
BIAS_STD = 0.1
NORM_OFFSET_STD = 0.05


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file: the named
    architecture, with every size taken from the file's published keys."""
    import jax.numpy as jnp
    from repro.configs import get_config

    m = config["model"]
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) models run on this path")
    return dataclasses.replace(
        get_config(config["arch"]),
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m.get("head_dim"),
        qkv_bias=bool(m.get("attention_bias", False)),
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        dtype=jnp.dtype(config["dtype"]))


# ---------------------------------------------------------------- weights

def shapes(config: Dict[str, Any], vocab_rows: int) -> Dict[str, tuple]:
    """Flat weight name -> shape for the published keys."""
    m = config["model"]
    L, d = m["num_hidden_layers"], m["hidden_size"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // H
    ff = m["intermediate_size"]
    out = {
        "embed": (vocab_rows, d), "final_norm": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, H * hd), "wk": (L, d, KV * hd), "wv": (L, d, KV * hd),
        "wo": (L, H * hd, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
    }
    if m.get("attention_bias"):
        out.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    return out


def std(name: str, shape: tuple) -> float:
    if name == "embed":
        return EMBED_SCALE * shape[-1] ** -0.5
    if name in ("bq", "bk", "bv"):
        return BIAS_STD
    if name in ("ln1", "ln2", "final_norm"):
        return NORM_OFFSET_STD
    fan_in = shape[-2] ** -0.5                # a projection
    return QK_SCALE * fan_in if name in ("wq", "wk") else fan_in


def to_program(w: Dict[str, Any], model) -> Dict[str, Any]:
    """The program's parameter tree over the same arrays; raises if the
    program's own layout (``Model.init``) differs in any leaf."""
    import jax

    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in w}
    tree = {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": {"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                       "mlp": {k: w[k] for k in
                               ("w_gate", "w_up", "w_down")}}}
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"the program's parameter layout changed:\n"
                         f"  benchmark {got}\n  program   {exp}")
    return tree


# -------------------------------------------------------------- reference

def logits_at(config, w, seq, rows, mode: str = "f32"):
    """The plain float32 reference's logits at positions ``rows`` of
    ``seq`` (``reference/dense_gqa.py``); ``mode="fp8"`` is the
    control."""
    return reference.logits_at(config, w, seq, rows, mode=mode)


# ------------------------------------------------------------------- work

def dims(config: Dict) -> Dict[str, int]:
    m = config["model"]
    H = m["num_attention_heads"]
    return dict(L=m["num_hidden_layers"], d=m["hidden_size"], H=H,
                KV=m["num_key_value_heads"], ff=m["intermediate_size"],
                V=m["vocab_size"],
                hd=m.get("head_dim") or m["hidden_size"] // H)


def layer_weights(config: Dict) -> int:
    """Weights of one layer's matrix products."""
    k = dims(config)
    d, hd = k["d"], k["hd"]
    return (d * (k["H"] + 2 * k["KV"]) * hd + k["H"] * hd * d
            + 3 * d * k["ff"])


def token_flops(config: Dict, pos: int, logits: bool) -> int:
    """One token at position ``pos`` through every layer, plus its
    logits if it is sampled from."""
    k = dims(config)
    f = k["L"] * (2 * layer_weights(config)
                  + 4 * k["H"] * k["hd"] * (pos + 1))
    return f + (2 * k["d"] * k["V"] if logits else 0)


def prefill_flops(config: Dict, start: int, end: int) -> int:
    """Positions [start, end) of a prompt; logits at the last only."""
    k = dims(config)
    n = end - start
    if n <= 0:
        return 0
    attn_keys = (start + 1 + end) * n // 2          # sum of (p + 1)
    return (k["L"] * (2 * layer_weights(config) * n
                      + 4 * k["H"] * k["hd"] * attn_keys)
            + 2 * k["d"] * k["V"])


def decode_step_bytes(config: Dict, contexts: Iterable[int],
                      weight_bytes: int = 2, kv_bytes: int = 2) -> int:
    """Least HBM traffic of one decode step over slots whose tokens sit
    at positions ``contexts``: every weight read once (the tied
    embedding as the logits' matrix), each slot's keys and values up to
    its position read, and its new key and value written."""
    k = dims(config)
    contexts = list(contexts)
    weights = k["L"] * layer_weights(config) + k["V"] * k["d"]
    kv_row = k["L"] * 2 * k["KV"] * k["hd"] * kv_bytes
    return (weights * weight_bytes
            + sum(kv_row * (p + 1) for p in contexts)
            + kv_row * len(contexts))
