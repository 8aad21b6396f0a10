"""Median of the program's ``engine.decode.wait.copy`` span: a decode step's tokens from the
device to the host once the step is done (``.ready`` before it is the device not done yet),
and the waiting thread's wake-up."""
from benchmarks.lib import readers, stalls


def read(run):
    return readers.span_ms_p50(run, stalls.COPY)


def example(run):
    stalls.example_cycle(run, 1.06, 0.005, 0.003)
