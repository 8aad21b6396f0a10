"""Own device milliseconds of the state-space layers in one run of the engine's prefill program
(a chunk of one prompt), by the program's scopes inside ``hvd_ssm``: ``.proj``
(``hvd_ssm_proj``), ``.conv`` (``hvd_ssm_conv``), ``.scan`` (``hvd_ssm_scan``: the chunked scan
from the slot's carried state, the state's read and write) and ``.gate`` (``hvd_ssm_gate``)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_prefill"
SCOPES = {"proj": "hvd_ssm_proj", "conv": "hvd_ssm_conv", "scan": "hvd_ssm_scan",
          "gate": "hvd_ssm_gate"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Two prefill runs: 10 ms of projections, 1 of convolution, 6 of scan, 0.5 of gate."""
    prefill = run.trace.scope_op_s.setdefault("jit_" + PROGRAM, {})
    prefill["hvd_ssm/hvd_ssm_proj"] = {"fusion": 0.010}
    prefill["hvd_ssm/hvd_ssm_conv"] = {"fusion": 0.001}
    prefill["hvd_ssm/hvd_ssm_scan"] = {"fusion": 0.005, "while": 0.001}
    prefill["hvd_ssm/hvd_ssm_gate"] = {"fusion": 0.0005}
