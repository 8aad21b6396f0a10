"""Own device milliseconds of the gated DeltaNet (GDN) layers in one run of the engine's decode
program, by the program's scopes inside ``hvd_gdn``: ``.proj`` (``hvd_gdn_proj``: q, k and v,
the decay's and beta's inputs, the output gate and the output projection), ``.conv``
(``hvd_gdn_conv``: the three causal convolutions, their tails' read and write), ``.scan``
(``hvd_gdn_scan``: the L2 norms, the decay, the one-step rule over every slot, the state's read
and write) and ``.gate`` (``hvd_gdn_gate``: the head norm and the output gate's silu)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_decode"
SCOPES = {"proj": "hvd_gdn_proj", "conv": "hvd_gdn_conv", "scan": "hvd_gdn_scan",
          "gate": "hvd_gdn_gate"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Four decode runs: 8 ms of projections, 1 of convolution, 24 of recurrence, 0.4 of gate."""
    decode = run.trace.scope_op_s["jit_" + PROGRAM]
    decode["hvd_gdn/hvd_gdn_proj"] = {"fusion": 0.008}
    decode["hvd_gdn/hvd_gdn_conv"] = {"fusion": 0.001}
    decode["hvd_gdn/hvd_gdn_scan"] = {"fusion": 0.016, "dynamic-update-slice": 0.008}
    decode["hvd_gdn/hvd_gdn_gate"] = {"fusion": 0.0004}
