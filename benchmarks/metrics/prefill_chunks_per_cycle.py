"""Prefill chunks dispatched per scheduling cycle: the program's ``engine.prefill.dispatch``
spans over its ``serve.cycle`` spans."""
from benchmarks.lib.trace import Span

CHUNK, CYCLE = "hvd.engine.prefill.dispatch", "hvd.serve.cycle"


def read(run):
    if run.trace is None:
        return None
    cycles = len(run.trace.span_seconds(CYCLE))
    chunks = len(run.trace.span_seconds(CHUNK))
    return chunks / cycles if cycles and chunks else None


def example(run):
    """Two cycles, one of them with a chunk inside its ``serve.prefill``."""
    n = len(run.trace.spans)
    run.trace.spans += [
        Span(CYCLE, 0.50, 0.02, -1), Span("hvd.serve.prefill", 0.50, 0.01, n),
        Span(CHUNK, 0.501, 0.002, n + 1), Span(CYCLE, 0.53, 0.01, -1)]
