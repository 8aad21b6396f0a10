"""Slowest step of the traced window over the median step: a host stall shows here."""
from benchmarks.lib import readers


def read(run):
    return readers.step_ms_max_over_p50(run)
