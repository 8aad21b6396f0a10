"""Least time the chip's matrix units could take over one prefill chunk of the gated DeltaNet
hybrid model, over the chunk's device time. The operations a chunk must do
(``benchmarks/roofline/olmo_hybrid.py``): every product with a weight for the chunk's real
tokens, the delta rule's three products with the state a token, the scores and the weighted sum
over the causal live context, the head for the prompt's last row; the window's total over its
chunks, at the bf16 peak of ``peaks.json``; over the mean device time of a run of
``jit_hvd_serve_prefill``."""
from benchmarks.lib import programs


def read(run):
    flops = run.counters.get("prefill_required_flops")
    chunks = run.counters.get("prefill_chunks")
    ms = programs.ms_per_run(run, "hvd_serve_prefill", holds_kernel=False)
    model = run.program.get("model", {})
    if not flops or not chunks or not ms or not run.peak \
            or "linear_key_head_dim" not in model:
        return None
    return 100.0 * flops / chunks / run.peak["flops_bf16"] / (ms * 1e-3)


def example(run):
    """Three chunks of 1.2 TFLOP each of the cut configuration."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "olmo_hybrid_7b.json")) as f:
        run.program["model"] = json.load(f)
    run.counters.update(prefill_required_flops=3 * 1.2e12, prefill_chunks=3)
