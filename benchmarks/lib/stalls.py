"""The serve loop's host pauses in a traced window, from the program's own
spans on the profiler's clock (``Summary.spans``): a cycle's host turn, the
interpreter's garbage collections, and the two halves of a readback. Shared
by the readers in ``metrics/`` and by ``tools/idle_blocks.py``."""

from __future__ import annotations

from typing import List, Optional, Sequence

from benchmarks.lib import window
from benchmarks.lib.trace import Span

CYCLE = "hvd.serve.cycle"
WAIT = "hvd.engine.decode.wait"
READY, COPY = WAIT + ".ready", WAIT + ".copy"
SEE = "hvd.serve.see"
GC = "hvd.host.gc."                 # + gen0 / gen1 / gen2


def spans_of(run) -> List[Span]:
    return run.trace.spans if run.trace is not None else []


def seconds_of(run, name: str) -> List[float]:
    return run.trace.span_seconds(name) if run.trace is not None else []


def host_turns(spans: Sequence[Span]) -> List[float]:
    """Seconds of each scheduling cycle less the waits for the device beneath
    it (``engine.decode.wait``, found by the parent chain): what the host
    took for itself, a pause of the interpreter or of its thread included."""
    waited = {}
    for s in spans:
        if s.name != WAIT:
            continue
        p = s.parent
        while p >= 0 and spans[p].name != CYCLE:
            p = spans[p].parent
        if p >= 0:
            waited[p] = waited.get(p, 0.0) + s.seconds
    return [s.seconds - waited.get(i, 0.0)
            for i, s in enumerate(spans) if s.name == CYCLE]


def collections(run) -> Optional[List[float]]:
    """Seconds of every collection that started inside the window; None
    where the program is one that does not say (it has no ``serve.see``
    either: the hook and that span came together), so that its silence is
    not read as a window without a collection."""
    spans = spans_of(run)
    if not any(s.name == SEE for s in spans):
        return None
    return [s.seconds for s in spans if s.name.startswith(GC)]


def ms_p50(seconds: Sequence[float]) -> Optional[float]:
    return 1e3 * window.median(seconds) if seconds else None


def ms_max(seconds: Sequence[float]) -> Optional[float]:
    return 1e3 * max(seconds) if seconds else None


def example_cycle(run, start_s: float, cycle_s: float, wait_s: float) -> None:
    """One more cycle on the made-up run: ``serve.decode`` beneath it and
    under that a wait with its two halves and the read-in of the tokens."""
    n = len(run.trace.spans)
    run.trace.spans += [
        Span(CYCLE, start_s, cycle_s, -1),
        Span("hvd.serve.decode", start_s, cycle_s, n),
        Span(WAIT, start_s, wait_s, n + 1),
        Span(READY, start_s, 0.9 * wait_s, n + 2),
        Span(COPY, start_s + 0.9 * wait_s, 0.1 * wait_s, n + 2),
        Span(SEE, start_s + wait_s, 0.0002, n + 1)]
