"""Median host time of the program's ``serve.see`` span: the read-in of the step before (per
token an ``int()``, two appends, a histogram and a counter), inside ``serve.decode``."""
from benchmarks.lib import readers, stalls


def read(run):
    return readers.span_ms_p50(run, stalls.SEE)


def example(run):
    stalls.example_cycle(run, 0.95, 0.005, 0.003)
