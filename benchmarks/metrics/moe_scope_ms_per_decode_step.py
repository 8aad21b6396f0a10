"""Own device milliseconds of the expert block in one run of the engine's decode program, by
the program's scopes inside ``hvd_moe``: ``.router`` (``hvd_moe_router``: the float32 router
product, softmax, top-k and the routing counters), ``.experts`` (``hvd_moe_experts``: the held
experts' three products over the rows present) and ``.combine`` (``hvd_moe_combine``: the held
experts' gates and the zero-compute experts' identity term)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_decode"
SCOPES = {"router": "hvd_moe_router", "experts": "hvd_moe_experts",
          "combine": "hvd_moe_combine"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Four decode runs: 2 ms of router, 24 of experts, 1 of combine."""
    decode = run.trace.scope_op_s["jit_" + PROGRAM]
    decode["hvd_moe/hvd_moe_router"] = {"fusion": 0.0015, "sort": 0.0005}
    decode["hvd_moe/hvd_moe_experts"] = {"fusion": 0.024}
    decode["hvd_moe/hvd_moe_combine"] = {"fusion": 0.001}
