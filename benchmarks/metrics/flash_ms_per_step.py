"""Device milliseconds of the three flash kernels per train step."""
from benchmarks.lib import readers


def read(run):
    return readers.flash_ms_per_step(run)
