"""Operations and bytes that the algorithm needs, from the published
sizes and each token's real context length.

Nothing here depends on how the program does the work: a token at
position ``p`` (0-based) attends to the ``p + 1`` keys up to and
including its own, whatever the cache holds or gathers; logits are
needed only where a token is sampled (the last prompt position and every
decoded token); a prefix that an earlier request computed is not
computed again.  One multiply-add counts as two operations; only matrix
products and attention are counted (norms, RoPE, biases and the softmax
are a rounding error beside them).
"""
from __future__ import annotations

from typing import Dict, Iterable


def dims(m: Dict) -> Dict[str, int]:
    H = m["num_attention_heads"]
    return dict(L=m["num_hidden_layers"], d=m["hidden_size"], H=H,
                KV=m["num_key_value_heads"], ff=m["intermediate_size"],
                V=m["vocab_size"],
                hd=m.get("head_dim") or m["hidden_size"] // H)


def layer_weights(m: Dict) -> int:
    """Weights of one layer's matrix products."""
    k = dims(m)
    d, hd = k["d"], k["hd"]
    return (d * (k["H"] + 2 * k["KV"]) * hd + k["H"] * hd * d
            + 3 * d * k["ff"])


def token_flops(m: Dict, pos: int, logits: bool) -> int:
    """One token at position ``pos`` through every layer, plus its
    logits if it is sampled from."""
    k = dims(m)
    f = k["L"] * (2 * layer_weights(m) + 4 * k["H"] * k["hd"] * (pos + 1))
    return f + (2 * k["d"] * k["V"] if logits else 0)


def prefill_flops(m: Dict, start: int, end: int) -> int:
    """Positions [start, end) of a prompt; logits at the last only."""
    k = dims(m)
    n = end - start
    if n <= 0:
        return 0
    attn_keys = (start + 1 + end) * n // 2          # sum of (p + 1)
    return (k["L"] * (2 * layer_weights(m) * n
                      + 4 * k["H"] * k["hd"] * attn_keys)
            + 2 * k["d"] * k["V"])


def decode_step_bytes(m: Dict, contexts: Iterable[int],
                      weight_bytes: int = 2, kv_bytes: int = 2) -> int:
    """Least HBM traffic of one decode step over slots whose tokens sit
    at positions ``contexts``: every weight read once (the tied
    embedding as the logits' matrix), each slot's keys and values up to
    its position read, and its new key and value written."""
    k = dims(m)
    contexts = list(contexts)
    weights = k["L"] * layer_weights(m) + k["V"] * k["d"]
    kv_row = k["L"] * 2 * k["KV"] * k["hd"] * kv_bytes
    return (weights * weight_bytes
            + sum(kv_row * (p + 1) for p in contexts)
            + kv_row * len(contexts))
