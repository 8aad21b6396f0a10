"""Share of all token-to-expert assignments that went to a zero-compute (identity) expert:
work the expert block did not have to do (routing counters of the program)."""


def read(run):
    zero = run.counters.get("moe_assignments_zero")
    rest = (run.counters.get("moe_assignments_held", 0)
            + run.counters.get("moe_assignments_absent", 0))
    return zero / (zero + rest) if zero else None


def example(run):
    run.counters.update(moe_assignments_held=250, moe_assignments_zero=4000,
                        moe_assignments_absent=7750)
