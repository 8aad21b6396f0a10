"""Assignments to the routed experts held on this chip per token and layer, prefill and decode
together (routing counters of the program, kept on the device): ``moe_topk`` times the share of
all assignments that went to a held expert. Under a uniform router: top-k x held / router width."""


def read(run):
    held = run.counters.get("moe_assignments_held")
    rest = (run.counters.get("moe_assignments_zero", 0)
            + run.counters.get("moe_assignments_absent", 0))
    if not held:
        return None
    return run.program["model"]["moe_topk"] * held / (held + rest)


def example(run):
    """1000 token-layers of top-12: 250 to held experts, 4000 to zero-compute ones."""
    run.program["model"] = {"moe_topk": 12}
    run.counters.update(moe_assignments_held=250, moe_assignments_zero=4000,
                        moe_assignments_absent=7750)
