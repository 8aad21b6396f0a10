"""Rows routed to the busiest held expert over the mean of the held experts, in the window
(routing counters of the program, ``moe_expert_rows.<j>``): 1 is an even load."""

PREFIX = "moe_expert_rows."


def read(run):
    rows = [v for k, v in run.counters.items() if k.startswith(PREFIX)]
    if not rows or not sum(rows):
        return None
    return max(rows) * len(rows) / sum(rows)


def example(run):
    """Four held experts: 10, 20, 30 and 60 rows."""
    for j, rows in enumerate((10, 20, 30, 60)):
        run.counters[f"{PREFIX}{j}"] = rows
