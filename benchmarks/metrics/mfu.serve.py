"""Whole-replica share of the chip's bf16 peak: required forward operations of every token
prefilled and generated in the window over the window's time."""

from benchmarks.lib import readers


def read(run):
    return readers.share_of_peak(run, run.counters.get("required_flops"))
