"""Own device milliseconds of the latent-attention blocks in one run of the engine's prefill
program (a chunk of one prompt), by the program's scopes: ``.proj`` (``hvd_mla_proj``: the
low-rank q and kv projections, the rotary and ``Wo``), ``.expand`` (``hvd_mla_expand``: the
heads' keys and values expanded from every one of the sequence's ``max_seq`` cached latent
rows) and ``.attention`` (``hvd_attention``: the gather of the sequence's pages, the scores
over ``max_seq`` columns, the masked softmax and the weighted sum)."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_prefill"
SCOPES = {"proj": "hvd_mla_proj", "expand": "hvd_mla_expand", "attention": "hvd_attention"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """Two prefill runs: 3 ms of projections, 8 of expansion, 12 of attention."""
    prefill = run.trace.scope_op_s.setdefault("jit_" + PROGRAM, {})
    prefill["hvd_mla_proj"] = {"fusion": 0.006}
    prefill["hvd_mla_expand"] = {"convolution": 0.016}
    prefill["hvd_attention"] = {"fusion": 0.020, "gather": 0.004}
