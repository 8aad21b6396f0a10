"""Own device milliseconds of the state-space layers in one run of the engine's decode program,
by the program's scopes inside ``hvd_ssm``: ``.proj`` (``hvd_ssm_proj``: the input and output
projections), ``.conv`` (``hvd_ssm_conv``: the causal convolution, its tail's read and write),
``.scan`` (``hvd_ssm_scan``: the one-step recurrence over every slot, the state's read and
write) and ``.gate`` (``hvd_ssm_gate``: the gated norm)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_decode"
SCOPES = {"proj": "hvd_ssm_proj", "conv": "hvd_ssm_conv", "scan": "hvd_ssm_scan",
          "gate": "hvd_ssm_gate"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Four decode runs: 12 ms of projections, 2 of convolution, 28 of recurrence, 1 of gate."""
    decode = run.trace.scope_op_s["jit_" + PROGRAM]
    decode["hvd_ssm/hvd_ssm_proj"] = {"fusion": 0.012}
    decode["hvd_ssm/hvd_ssm_conv"] = {"fusion": 0.002}
    decode["hvd_ssm/hvd_ssm_scan"] = {"fusion": 0.020, "dynamic-update-slice": 0.008}
    decode["hvd_ssm/hvd_ssm_gate"] = {"fusion": 0.001}
