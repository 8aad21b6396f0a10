"""Least time the chip's matrix units could take over one prefill chunk of the DeepSeek-V3
stack (Kimi K2's), over the chunk's device time. The operations a chunk must do
(``benchmarks/roofline/kimi_k2.py``): every product with a weight outside the routed experts
for the chunk's real tokens, the expansion of their own latent rows only, the scores and the
weighted sum over the causal live context only, in the cheaper of the two forms of latent
attention, and a held expert's products for each of the chunk's tokens routed to it (the
program's counter, prefill only); the window's total over its chunks, at the bf16 peak of
``peaks.json``; over the mean device time of a run of ``jit_hvd_serve_prefill``."""
from benchmarks.lib import programs


def read(run):
    flops = run.counters.get("prefill_required_flops")
    chunks = run.counters.get("prefill_chunks")
    ms = programs.ms_per_run(run, "hvd_serve_prefill", holds_kernel=False)
    if not flops or not chunks or not ms or not run.peak:
        return None
    return 100.0 * flops / chunks / run.peak["flops_bf16"] / (ms * 1e-3)


def example(run):
    """Three chunks of 1.2 TFLOP each."""
    run.counters.update(prefill_required_flops=3 * 1.2e12, prefill_chunks=3)
