"""Whole-step share of the chips' bf16 peak: required forward+backward operations
(no recompute) of the counted steps over the window's time."""
from benchmarks.lib import readers


def read(run):
    return readers.train_mfu(run)
