"""Own device milliseconds of the delta-rule (KDA) layers in one run of the engine's decode
program, by the program's scopes inside ``hvd_kda``: ``.proj`` (``hvd_kda_proj``: q, k and v,
the two low-rank gates, beta and the output projection), ``.conv`` (``hvd_kda_conv``: the three
causal convolutions, their tails' read and write), ``.scan`` (``hvd_kda_scan``: the L2 norms,
the decay, the one-step recurrence over every slot, the state's read and write) and ``.gate``
(``hvd_kda_gate``: the head norm and the output gate)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_decode"
SCOPES = {"proj": "hvd_kda_proj", "conv": "hvd_kda_conv", "scan": "hvd_kda_scan",
          "gate": "hvd_kda_gate"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Four decode runs: 8 ms of projections, 1 of convolution, 24 of recurrence, 0.4 of gate."""
    decode = run.trace.scope_op_s["jit_" + PROGRAM]
    decode["hvd_kda/hvd_kda_proj"] = {"fusion": 0.008}
    decode["hvd_kda/hvd_kda_conv"] = {"fusion": 0.001}
    decode["hvd_kda/hvd_kda_scan"] = {"fusion": 0.016, "dynamic-update-slice": 0.008}
    decode["hvd_kda/hvd_kda_gate"] = {"fusion": 0.0004}
