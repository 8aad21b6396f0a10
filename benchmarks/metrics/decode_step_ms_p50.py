"""Median host time of one ``ServeEngine.decode_step`` call: dispatch to tokens read back."""
from benchmarks.lib import readers


def read(run):
    return readers.step_ms_p50(run)
