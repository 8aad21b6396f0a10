"""Device milliseconds of one run of the engine's decode program (``jit_hvd_serve_decode``).
Host time of the same call (``decode_step_ms_p50``) less this is dispatch, readback and the
wait behind a prefill chunk dispatched just before."""
from benchmarks.lib import programs


def read(run):
    return programs.ms_per_run(run, "hvd_serve_decode", holds_kernel=True)
