"""Median host time of the program's ``engine.decode.dispatch`` span: block tables and
lengths uploaded and the decode program enqueued; the device may still be idle."""
from benchmarks.lib import readers


def read(run):
    return readers.span_ms_p50(run, "hvd.engine.decode.dispatch")
