"""Own device milliseconds of the latent-attention blocks' projections in one run of the
engine's decode program: ``.proj`` (``hvd_mla_proj``: the low-rank q and kv projections, the
absorption of ``Wkvb`` into the query and the output, and ``Wo``). The scores over the latent
cache and the weighted sum stand under ``scope_ms_per_decode_step.attention``, the latent
row's scatter under ``.kv_write``."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_decode"
SCOPES = {"proj": "hvd_mla_proj"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Four decode runs, 8 ms of projections."""
    run.trace.scope_op_s["jit_" + PROGRAM]["hvd_mla_proj"] = {"fusion": 0.008}
