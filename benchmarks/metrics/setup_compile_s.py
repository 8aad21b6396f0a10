"""Seconds JAX spent in backend compiles (cache reads included) during set-up."""


def read(run):
    return run.setup.get("compile_s")
