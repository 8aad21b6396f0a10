"""Collective time in the trace during which no compute ran on that device, per step."""


def read(run):
    if run.trace is None or not run.trace.exposed_collective_s:
        return None
    return 1e3 * run.trace.exposed_collective_s / run.counters["steps_in_trace"]
