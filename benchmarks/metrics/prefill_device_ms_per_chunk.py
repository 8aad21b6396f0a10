"""Device milliseconds of one run of the engine's prefill program (``jit_hvd_serve_prefill``):
one chunk of one prompt, whatever its bucket."""
from benchmarks.lib import programs


def read(run):
    return programs.ms_per_run(run, "hvd_serve_prefill", holds_kernel=False)
