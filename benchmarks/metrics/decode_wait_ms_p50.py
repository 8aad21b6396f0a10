"""Median host time of the program's ``engine.decode.wait`` span: the readback of a decode
step's tokens, which holds the device's time for the step and for a prefill chunk before it."""
from benchmarks.lib import readers


def read(run):
    return readers.span_ms_p50(run, "hvd.engine.decode.wait")
