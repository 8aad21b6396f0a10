"""Device milliseconds of hvd_paged_decode per decode step (all layers)."""


def read(run):
    if run.trace is None or not run.unit_s:
        return None
    seconds = run.trace.kernel_s("hvd_paged_decode")
    return 1e3 * seconds / len(run.unit_s) if seconds else None
