"""Arguments + temporaries of the compiled step per chip (memory_analysis)."""
from benchmarks.lib import readers


def read(run):
    return readers.step_hbm_gb(run)
