"""hvd_paged_decode: least time the chip could take for its calls (one per layer and
decode step, over the keys cached in the slots in use) over their device time."""
from benchmarks.roofline import flash_attention, paged_decode


def read(run):
    if run.trace is None or not run.peak:
        return None
    seconds = run.trace.kernel_s("hvd_paged_decode")
    if not seconds:
        return None
    p = run.program
    least = sum(flash_attention.least_seconds(
        paged_decode.call(keys, p["heads"], p["head_dim"]), run.peak)
        for keys in run.counters["decode_keys"] if keys)
    return 100.0 * least * p["layers"] / seconds
