"""Share of the window's prefill chunks that went on from the gated DeltaNet state and convolution
tails the chunk before had stored in the slot (``start > 0``), over all chunks; the rest opened a
prompt from a zero state (the program's counters on the device, ``engine.stats()["ssm"]``)."""


def read(run):
    carried = run.counters.get("ssm_chunks_carried")
    resets = run.counters.get("ssm_resets")
    if carried is None or resets is None or not carried + resets:
        return None
    return carried / (carried + resets)


def example(run):
    """60 prompts in 340 chunks."""
    run.counters.update(ssm_resets=60, ssm_chunks_carried=280)
