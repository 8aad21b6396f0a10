"""Least time the chip's memory could take to feed one decode step of the gated DeltaNet hybrid
model, over the step's device time. The bytes a step must move (``benchmarks/roofline/
olmo_hybrid.py``): every weight but the embedding, the embedding rows of the tokens, the K and V
rows of the live context, and the live slots' delta-rule state and convolution tails read AND
written once each at the state's dtype; at the HBM peak of ``peaks.json``; over the mean device
time of a run of ``jit_hvd_serve_decode``."""
from benchmarks.lib import programs
from benchmarks.roofline import olmo_hybrid as cost


def read(run):
    steps = [keys for keys in run.counters.get("decode_keys", []) if keys]
    ms = programs.ms_per_run(run, "hvd_serve_decode", holds_kernel=True)
    model = run.program.get("model", {})
    if not steps or not ms or not run.peak or "linear_key_head_dim" not in model:
        return None
    least = cost.decode_step_bytes(
        model, rows=run.program["slots"],
        cached_tokens=sum(sum(keys) for keys in steps) / len(steps),
        live_slots=sum(len(keys) for keys in steps) / len(steps))
    return 100.0 * least / run.peak["hbm_bytes_per_s"] / (ms * 1e-3)


def example(run):
    """The cut configuration; 2 live slots of 64 holding 300 and 500 keys."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "olmo_hybrid_7b.json")) as f:
        run.program["model"] = json.load(f)
    run.program["slots"] = 64
