"""Median host time from one step's completion to the next, traced window."""
from benchmarks.lib import readers


def read(run):
    return readers.step_ms_p50(run)
