"""Own device milliseconds of the gated DeltaNet (GDN) layers in one run of the engine's prefill
program (a chunk of one prompt), by the program's scopes inside ``hvd_gdn``: ``.proj``
(``hvd_gdn_proj``), ``.conv`` (``hvd_gdn_conv``), ``.scan`` (``hvd_gdn_scan``: the chunked delta
rule from the slot's carried state, its triangular solve, the state's read and write) and
``.gate`` (``hvd_gdn_gate``)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_prefill"
SCOPES = {"proj": "hvd_gdn_proj", "conv": "hvd_gdn_conv", "scan": "hvd_gdn_scan",
          "gate": "hvd_gdn_gate"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Two prefill runs: 6 ms of projections, 0.6 of convolution, 5 of the chunked rule, 0.2 of
    gate."""
    prefill = run.trace.scope_op_s.setdefault("jit_" + PROGRAM, {})
    prefill["hvd_gdn/hvd_gdn_proj"] = {"fusion": 0.006}
    prefill["hvd_gdn/hvd_gdn_conv"] = {"fusion": 0.0006}
    prefill["hvd_gdn/hvd_gdn_scan"] = {"fusion": 0.003, "while": 0.0015, "custom-call": 0.0005}
    prefill["hvd_gdn/hvd_gdn_gate"] = {"fusion": 0.0002}
