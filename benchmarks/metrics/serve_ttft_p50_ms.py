"""Median time from submit to first token over every first token seen in the window."""
from benchmarks.lib import window


def read(run):
    ttft = run.counters.get("ttft_s")
    return window.median(ttft) * 1e3 if ttft else None
