"""Own device milliseconds of the delta-rule (KDA) layers in one run of the engine's prefill
program (a chunk of one prompt), by the program's scopes inside ``hvd_kda``: ``.proj``
(``hvd_kda_proj``), ``.conv`` (``hvd_kda_conv``), ``.scan`` (``hvd_kda_scan``: the chunked delta
rule from the slot's carried state, its triangular solve, the state's read and write) and
``.gate`` (``hvd_kda_gate``)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_prefill"
SCOPES = {"proj": "hvd_kda_proj", "conv": "hvd_kda_conv", "scan": "hvd_kda_scan",
          "gate": "hvd_kda_gate"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Two prefill runs: 6 ms of projections, 0.6 of convolution, 5 of the chunked rule, 0.2 of
    gate."""
    prefill = run.trace.scope_op_s.setdefault("jit_" + PROGRAM, {})
    prefill["hvd_kda/hvd_kda_proj"] = {"fusion": 0.006}
    prefill["hvd_kda/hvd_kda_conv"] = {"fusion": 0.0006}
    prefill["hvd_kda/hvd_kda_scan"] = {"fusion": 0.003, "while": 0.0015, "custom-call": 0.0005}
    prefill["hvd_kda/hvd_kda_gate"] = {"fusion": 0.0002}
