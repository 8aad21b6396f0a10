"""Bytes all-reduced per step, from the compiled HLO, in GB."""
from benchmarks.lib import readers


def read(run):
    total = readers.allreduce_bytes(run.program.get("hlo_text", ""))
    return total / 1e9 if total else None
