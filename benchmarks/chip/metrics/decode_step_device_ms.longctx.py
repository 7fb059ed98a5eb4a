"""Mean device milliseconds per launch of the decode step (trace)."""
from benchmarks.chip.work import exe_ms


def read(rec):
    return exe_ms(rec, "decode")
