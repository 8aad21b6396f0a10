"""hvd_flash_bwd_dq: least time the chip could take for its calls over their device time."""
from benchmarks.lib import readers
from benchmarks.roofline import flash_attention


def read(run):
    return readers.flash_roofline(run, "hvd_flash_bwd_dq",
                                  flash_attention.backward_dq)
