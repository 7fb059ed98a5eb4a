"""Helpers the per-layer readers share: what the window did, counted
from the harness's records, and device time per executable."""
from __future__ import annotations

from typing import Optional

from benchmarks.chip import spec


def prefilled(rec, lo: float, hi: float) -> int:
    """Requests whose prefill ended (first token) in [lo, hi)."""
    return sum(1 for r in rec.requests
               if r.token_times and lo <= r.token_times[0] < hi)


def window_prefilled(rec) -> int:
    return prefilled(rec, rec.window_start, rec.window_start + rec.window_s)


def flops_in(rec, lo: float, hi: float) -> int:
    """Operations the algorithm needed for the tokens that came out in
    [lo, hi): a prefill counts where its first token came out, from the
    end of any shared prefix to the end of the prompt; decoded token
    ``i >= 1`` attends from position ``len(prompt) + i - 1``."""
    config = rec.cell.config
    kind = spec.arch_kind(rec.cell)
    total = 0
    for r in rec.requests:
        n = len(r.prompt)
        for i, t in enumerate(r.token_times):
            if not lo <= t < hi:
                continue
            if i == 0:
                total += kind.prefill_flops(config, r.shared_prefix, n)
            else:
                total += kind.token_flops(config, n + i - 1, logits=True)
    return total


def exe_ms(rec, label: str) -> Optional[float]:
    """Mean device milliseconds per launch of the labelled executable in
    the traced window; None where the trace cannot tell it apart."""
    key = rec.exe_labels.get(label)
    if rec.trace is None or key is None:
        return None
    n = rec.trace.exe_launches.get(key, 0)
    if not n:
        return None
    return 1e3 * rec.trace.exe_time_s[key] / n


def mfu_percent(rec) -> Optional[float]:
    """Operations of the traced window over its device busy time, as a
    share of the chip's bf16 peak."""
    if rec.trace is None or rec.trace_span is None or not rec.peaks \
            or rec.trace.busy_s <= 0:
        return None
    lo, hi = rec.trace_span
    f = flops_in(rec, lo, hi)
    if f <= 0:
        return None
    return 100.0 * f / (rec.trace.busy_s * rec.peaks["bf16_flops_per_s"])
