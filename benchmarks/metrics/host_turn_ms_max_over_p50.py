"""The longest host turn of a cycle (``host_turn_ms_p50``) over the median one: a window that
held a pause of the host reads tens, one that held none reads 2-6 (the cycles that dispatch
several prefill chunks)."""
from benchmarks.lib import stalls, window


def read(run):
    turns = stalls.host_turns(stalls.spans_of(run))
    return max(turns) / window.median(turns) if turns else None


def example(run):
    """A cycle of 120 ms with 3 of them waiting: a pause."""
    stalls.example_cycle(run, 0.82, 0.120, 0.003)
