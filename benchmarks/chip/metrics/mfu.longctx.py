"""Whole step's share of the bf16 peak: the operations the algorithm
needed in the traced window over the device's busy time there."""
from benchmarks.chip.work import mfu_percent


def read(rec):
    return mfu_percent(rec)
