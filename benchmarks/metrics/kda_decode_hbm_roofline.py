"""Least time the chip's memory could take to feed one decode step of the delta-rule hybrid
model, over the step's device time. The bytes a step must move
(``benchmarks/roofline/solar_open2.py``): every weight outside the routed experts, the head's
slice, the embedding rows of the tokens, the matrices of each held expert that got at least one
row (the program's counter, decode steps only, summed over the layers), the K and V rows of the
live context, and the live slots' delta-rule state and convolution tails read AND written once
each at the state's dtype; at the HBM peak of ``peaks.json``; over the mean device time of a run
of ``jit_hvd_serve_decode``."""
from benchmarks.lib import programs
from benchmarks.roofline import solar_open2 as cost


def read(run):
    steps = [keys for keys in run.counters.get("decode_keys", []) if keys]
    active = run.counters.get("moe_decode_experts_active")
    ms = programs.ms_per_run(run, "hvd_serve_decode", holds_kernel=True)
    model = run.program.get("model", {})
    if not steps or not active or not ms or not run.peak or "linear_attn_config" not in model:
        return None
    least = cost.decode_step_bytes(
        model, rows=run.program["slots"], experts_with_rows=active / len(steps),
        cached_tokens=sum(sum(keys) for keys in steps) / len(steps),
        live_slots=sum(len(keys) for keys in steps) / len(steps))
    return 100.0 * least / run.peak["hbm_bytes_per_s"] / (ms * 1e-3)


def example(run):
    """The cut configuration; 2 live slots of 128; 150 of the 160 (layer, expert) pairs got a
    row in a mean step."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "solar_open2_250b.json")) as f:
        run.program["model"] = json.load(f)
    run.program["slots"] = 128
    run.counters["moe_decode_experts_active"] = 150 * len(run.counters["decode_keys"])
