"""Milliseconds the interpreter's garbage collector ran per second of the window: the
program's ``host.gc.gen0`` / ``gen1`` / ``gen2`` spans that started inside it. 0.0 is a window
that held no collection (a count, and no share); nothing where the program does not say."""
from benchmarks.lib import stalls
from benchmarks.lib.trace import Span


def read(run):
    seconds = stalls.collections(run)
    return None if seconds is None else 1e3 * sum(seconds) / run.trace.window_s


def example(run):
    """Two young collections of 0.3 ms inside a cycle's read-in."""
    stalls.example_cycle(run, 0.97, 0.005, 0.003)
    n = len(run.trace.spans)
    run.trace.spans += [Span(stalls.GC + "gen0", 0.9731, 0.0003, n - 1),
                        Span(stalls.GC + "gen0", 0.9736, 0.0003, n - 1)]
