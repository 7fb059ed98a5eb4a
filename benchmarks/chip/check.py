"""Whether a run is correct: what the timed path served, against the
plain float32 reference of the configuration's architecture kind
(``arch/<kind>.py``, ``logits_at``).

After the window a sample of the finished greedy requests is drawn from
the seed, the longest among them (most tokens served) always in it.  The
reference runs once over each sampled prompt followed by its served
tokens, and at every served position reads the gap by which the served
token's logit lies below the reference's best.  The widest gap of the
sample is held to the cell's limit (``cells/<cell>.json``, ``check``).
A window request that never finished, or finished short of its length,
fails the run as well.

Sampled requests share the greedy ones' batches and steps, so a fault in
the batched logits shows in the greedy tokens; their own draws are not
compared (at the seeded weights every token's probability lies near
uniform, and no limit on a drawn token's probability separates a sound
draw from a wrong one).

The control (``control_gaps``) puts the reference in the program's place
one precision below bfloat16 (float8_e4m3 operands): at the same
positions, the gap of the token that the lower precision ranks first.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmarks.chip import sampling, spec

#: the sample: at least this many served tokens and requests, and at
#: most this many requests
MIN_TOKENS, MIN_REQUESTS, MAX_REQUESTS = 300, 3, 8


def sample(requests, seed: int) -> List[Any]:
    ok = [r for r in requests if r.in_window and r.finished
          and r.temperature == 0.0 and len(r.tokens) == r.max_new_tokens]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (len(r.tokens), len(r.prompt)))
    rest = [r for r in ok if r is not longest]
    order = sampling.rng(seed, 2000).permutation(len(rest))
    out = [longest]
    for i in order:
        if len(out) >= MAX_REQUESTS or (
                len(out) >= MIN_REQUESTS and
                sum(len(r.tokens) for r in out) >= MIN_TOKENS):
            break
        out.append(rest[i])
    return out


def _positions(r):
    seq = np.concatenate([np.asarray(r.prompt, np.int64),
                          np.asarray(r.tokens[:-1], np.int64)])
    rows = len(r.prompt) - 1 + np.arange(len(r.tokens))
    return seq, rows


def served_gaps(cell, w, r) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    seq, rows = _positions(r)
    ref = spec.arch_kind(cell).logits_at(cell.config, w, seq, rows)
    toks = np.asarray(r.tokens)
    return ref.max(-1) - ref[np.arange(len(toks)), toks]


def control_gaps(cell, w, r) -> np.ndarray:
    """Per served position: the reference's gap of the token that the
    float8 control ranks first."""
    seq, rows = _positions(r)
    kind = spec.arch_kind(cell)
    ref = kind.logits_at(cell.config, w, seq, rows)
    ctl = kind.logits_at(cell.config, w, seq, rows, mode="fp8")
    pick = ctl.argmax(-1)
    return ref.max(-1) - ref[np.arange(len(pick)), pick]


def judge(rec, w, seed: int) -> Dict[str, Any]:
    """``{"correct": bool, "numbers": {name: {"value", "limit"}}}``."""
    limit = rec.cell.params.get("check", {}).get("max_logit_gap")
    picked = sample(rec.requests, seed)
    gap = max((float(served_gaps(rec.cell, w, r).max())
               for r in picked), default=None)
    failed = sum(r.in_window and not r.finished for r in rec.requests)
    short = sum(r.in_window and r.finished and
                len(r.tokens) != r.max_new_tokens for r in rec.requests)
    numbers = {
        "failed": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
        "max_logit_gap": {"value": gap, "limit": limit},
    }
    correct = (limit is not None and gap is not None and gap <= limit
               and failed == 0 and short == 0)
    return {"correct": bool(correct), "numbers": numbers,
            "requests": len(picked),
            "tokens": sum(len(r.tokens) for r in picked)}
