"""Device milliseconds of hvd_mla_decode per decode step (every latent attention block): the
paged latent decode kernel, read by its name. Nothing to read where decode gathers the block
tables instead."""


def read(run):
    if run.trace is None or not run.unit_s:
        return None
    seconds = run.trace.kernel_s("hvd_mla_decode")
    return 1e3 * seconds / len(run.unit_s) if seconds else None


def example(run):
    """Four decode steps, 12 ms of the kernel."""
    run.trace.op_total_s["hvd_mla_decode"] = 0.012
    run.trace.op_calls["hvd_mla_decode"] = 4 * 5
