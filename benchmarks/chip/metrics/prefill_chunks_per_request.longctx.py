"""Prefill chunks per request prefilled in the window (engine counter)."""
from benchmarks.chip.work import window_prefilled


def read(rec):
    n = window_prefilled(rec)
    return rec.counters["prefill_chunks"] / n if n else None
