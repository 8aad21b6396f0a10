"""The scheduler's own host time a cycle: the program's ``serve.cycle`` spans less the spans
directly inside them (``serve.retire`` / ``admit`` / ``prefill`` / ``decode``), mean."""

CYCLE = "hvd.serve.cycle"


def read(run):
    if run.trace is None:
        return None
    own = run.trace.span_self_seconds(CYCLE)
    return 1e3 * sum(own) / len(own) if own else None
