"""Least time the chip's memory could take to feed one decode step of the DeepSeek-V3 stack
(Kimi K2's), over the step's device time. The bytes a step must read
(``benchmarks/roofline/kimi_k2.py``): every weight outside the routed experts, the head's
slice, the embedding rows of the tokens, the matrices of each held expert that got at least one
row (the program's counter, decode steps only, summed over the layers) and the live context's
latent rows (576 numbers, not the 640 lanes a tile pads them to), once each layer; at the HBM
peak of ``peaks.json``; over the mean device time of a run of ``jit_hvd_serve_decode``."""
from benchmarks.lib import programs
from benchmarks.roofline import kimi_k2 as cost


def read(run):
    steps = [keys for keys in run.counters.get("decode_keys", []) if keys]
    active = run.counters.get("moe_decode_experts_active")
    ms = programs.ms_per_run(run, "hvd_serve_decode", holds_kernel=True)
    model = run.program.get("model", {})
    if not steps or not active or not ms or not run.peak \
            or "first_k_dense_replace" not in model:
        return None
    least = cost.decode_step_bytes(
        model, rows=run.program["slots"], experts_with_rows=active / len(steps),
        cached_tokens=sum(sum(keys) for keys in steps) / len(steps))
    return 100.0 * least / run.peak["hbm_bytes_per_s"] / (ms * 1e-3)


def example(run):
    """The cut configuration; 32 slots; 20 of the 48 (layer, expert) pairs got a row in a
    mean step."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "kimi_k2_7_code.json")) as f:
        run.program["model"] = json.load(f)
    run.program["slots"] = 32
    run.counters["moe_decode_experts_active"] = 20 * len(run.counters["decode_keys"])
