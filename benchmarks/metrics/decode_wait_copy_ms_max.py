"""The longest ``engine.decode.wait.copy`` of the window: an idle gap under ``engine.decode.wait``
with a long copy is the readback or the host's thread, with a long ``.ready`` the device's queue."""
from benchmarks.lib import stalls


def read(run):
    return stalls.ms_max(stalls.seconds_of(run, stalls.COPY))


def example(run):
    stalls.example_cycle(run, 1.07, 0.005, 0.003)
