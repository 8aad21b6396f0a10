"""Device milliseconds of the prefill programs per prompt token prefilled in the window.
The engine's programs carry no name in the trace (``jit__unknown``), so a prefill program is
told by what it runs: a run of a compiled program that holds no ``hvd_paged_decode`` call."""


def read(run):
    if run.trace is None or not run.counters.get("prefill_tokens"):
        return None
    seconds = run.trace.module_s(lacking="hvd_paged_decode")
    return 1e3 * seconds / run.counters["prefill_tokens"] if seconds else None
