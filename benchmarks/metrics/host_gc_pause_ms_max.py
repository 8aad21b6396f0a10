"""The longest single collection of the window (``host.gc.*`` spans): a young one is a few
tenths of a millisecond, a full one over a heap that was not set aside tens. Nothing where the
window held none."""
from benchmarks.lib import stalls
from benchmarks.lib.trace import Span


def read(run):
    return stalls.ms_max(stalls.collections(run) or [])


def example(run):
    """A full collection of 40 ms inside a cycle's read-in."""
    stalls.example_cycle(run, 1.00, 0.050, 0.003)
    n = len(run.trace.spans)
    run.trace.spans.append(Span(stalls.GC + "gen2", 1.004, 0.040, n - 1))
