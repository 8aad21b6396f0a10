"""Backend compiles that fell inside the measured window; there should be none."""


def read(run):
    return run.compiles_in_window
