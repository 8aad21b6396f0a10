"""Share of the decode steps that were enqueued while the step before was still unread: the
program's ``engine.decode.ahead`` spans (inside a dispatch, around the enqueue of such a step)
over its ``engine.decode.dispatch`` spans. 1 where the host never stands between two steps."""
from benchmarks.lib.trace import Span

AHEAD, DISPATCH = "hvd.engine.decode.ahead", "hvd.engine.decode.dispatch"


def read(run):
    if run.trace is None:
        return None
    steps = len(run.trace.span_seconds(DISPATCH))
    ahead = len(run.trace.span_seconds(AHEAD))
    return ahead / steps if steps and ahead else None


def example(run):
    """Two more dispatches, each with the enqueue of a step ahead inside it."""
    n = len(run.trace.spans)
    run.trace.spans += [
        Span(DISPATCH, 0.70, 0.0012, -1), Span(AHEAD, 0.7004, 0.0007, n),
        Span(DISPATCH, 0.71, 0.0012, -1), Span(AHEAD, 0.7104, 0.0007, n + 2)]
