"""Device milliseconds of the prefill step per request: its mean device
time per launch in the trace, times the window's chunks per request."""
from benchmarks.chip.work import exe_ms, window_prefilled


def read(rec):
    ms, n = exe_ms(rec, "prefill"), window_prefilled(rec)
    if ms is None or not n:
        return None
    return ms * rec.counters["prefill_chunks"] / n
