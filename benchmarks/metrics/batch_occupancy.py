"""Mean occupied share of the decode slots over the window's decode steps (scheduler counters)."""


def read(run):
    return run.counters.get("batch_occupancy")
