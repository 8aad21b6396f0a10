"""Median of a scheduling cycle's host turn: the program's ``serve.cycle`` span less the
``engine.decode.wait`` spans beneath it, so what the host took for retiring, admitting, the
dispatches and the read-in of the step before, whatever the device was doing meanwhile."""
from benchmarks.lib import stalls


def read(run):
    return stalls.ms_p50(stalls.host_turns(stalls.spans_of(run)))


def example(run):
    """A cycle of 5 ms with 3 of them waiting."""
    stalls.example_cycle(run, 0.80, 0.005, 0.003)
