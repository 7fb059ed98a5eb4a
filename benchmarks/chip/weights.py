"""Seeded weights for a dense GQA configuration, made on the device in
one jitted call, in the type they are served in.

The benchmark owns the weights: the program under test and the plain
reference both read the same arrays, so neither makes what the other is
compared with.  ``to_program`` arranges them in the program's parameter
tree (no copies) and checks that tree against ``Model.init``'s shapes.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

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


def shapes(m: Dict[str, Any], vocab_rows: int) -> Dict[str, tuple]:
    """Flat weight name -> shape for the published keys ``m``."""
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


def _std(name: str, shape: tuple) -> float:
    if name == "embed":
        return EMBED_SCALE * shape[-1] ** -0.5
    if name in ("bq", "bk", "bv"):
        return BIAS_STD
    if name in ("ln1", "ln2", "final_norm"):
        return NORM_OFFSET_STD
    fan_in = shape[-2] ** -0.5                # a projection
    return QK_SCALE * fan_in if name in ("wq", "wk") else fan_in


def make(m: Dict[str, Any], vocab_rows: int, seed: int, dtype):
    """All weights from ``seed``, on the default device, in ``dtype``."""
    spec = shapes(m, vocab_rows)
    names = sorted(spec)

    def build(key):
        keys = jax.random.split(key, len(names))
        return {n: (jax.random.truncated_normal(k, -2.0, 2.0, spec[n],
                                                jnp.float32)
                    * _std(n, spec[n])).astype(dtype)
                for n, k in zip(names, keys)}

    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    return jax.block_until_ready(jax.jit(build)(key))


def to_program(w: Dict[str, Any], model) -> Dict[str, Any]:
    """The program's parameter tree over the same arrays; raises if the
    program's own layout (``Model.init``) differs in any leaf."""
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
