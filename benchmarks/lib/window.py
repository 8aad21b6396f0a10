"""How a measured window is taken.

The clock starts at a barrier after the last warm-up step and stops at the
completion of the last unit of work that is counted: the first one to end at
or after ``seconds``. A rate is all counted work over that elapsed time;
nothing is divided by ``seconds`` and nothing the device has not finished is
counted. A fixed, small number of steps is kept in flight so the device never
waits for the host between steps."""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import statistics
import sys
import time
from typing import Any, Callable, Dict, List, Tuple


class Spans:
    """The benchmark's own host spans: kept in memory, and written into the
    profiler's trace (``TraceAnnotation``) while one is being taken, so the
    idle gaps of the device can be named by what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.rows: List[Tuple[str, float, float]] = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.rows.append((name, t0, t1))


@contextlib.contextmanager
def measured(compile_log: Any, session: Any = None):
    """Around a measured window: the profiler session, where one is taken,
    and the count of compiles that fall inside. What set-up left on the
    host's heap is set aside first, so that no full garbage collection falls
    inside the window and a run that compiled collects no more than one that
    read the cache (the first serve run of a set read 1.5 % low; one ResNet
    run of twelve had a step of 655 ms among steps of 97 ms; my chip runs,
    PR 24)."""
    if session is not None:
        session.start()
    compile_log.mark_window(True)
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        compile_log.mark_window(False)
        gc.unfreeze()
        if session is not None:
            session.stop()


def length(seconds: float, traffic: Any, trace: int) -> float:
    """How long this run's window is. A traced run measures the traffic
    file's ``trace_seconds`` where ``--seconds`` asks for more (the profiler
    keeps every event of the window in memory and the reduction reads them
    all), and says so: on standard error here, and in the result line's
    ``window``."""
    if not trace:
        return seconds
    short = min(seconds, float(traffic["trace_seconds"]))
    if short < seconds:
        print(f"benchmark: --trace 1 measures {short:g} s of the {seconds:g} s "
              f"asked for (trace_seconds of the traffic file)",
              file=sys.stderr)
    return short


@dataclasses.dataclass
class StepWindow:
    ends_s: List[float]             # completion time of each counted step
    drained: int                    # dispatched, finished after, not counted

    @property
    def counted(self) -> int:
        return len(self.ends_s)

    @property
    def elapsed_s(self) -> float:
        return self.ends_s[-1]

    @property
    def step_s(self) -> List[float]:
        return [b - a for a, b in zip([0.0] + self.ends_s[:-1], self.ends_s)]


def run_steps(dispatch: Callable[[int], Any], wait: Callable[[Any], None],
              seconds: float, in_flight: int = 2,
              clock: Callable[[], float] = time.perf_counter,
              spans: Spans = None) -> StepWindow:
    """Drive steps for at least ``seconds``. ``dispatch(k)`` enqueues step k
    and returns a handle; ``wait(handle)`` returns when that step's result has
    been read back. The caller has just passed a barrier: nothing is in
    flight."""
    spans = spans or Spans()
    pending: collections.deque = collections.deque()
    ends: List[float] = []
    k = 0
    t0 = clock()
    while True:
        while len(pending) < in_flight:
            with spans.span("bench.dispatch"):
                pending.append(dispatch(k))
            k += 1
        with spans.span("bench.readback"):
            wait(pending.popleft())
        t = clock() - t0
        ends.append(t)
        if t >= seconds:
            break
    drained = len(pending)
    for handle in pending:          # finished after the clock stopped
        wait(handle)
    return StepWindow(ends, drained)


def counter_marks(counters: Dict[str, Any], kinds_own: Tuple[str, ...]
                  ) -> Dict[str, Any]:
    """Where a family's running totals (``program.counters()``) stand at the
    window's start: a number as it is, a list (which only grows) by its
    length. A name the traffic kind uses itself (``kinds_own``) is refused
    here, before the window: one of the two would be lost."""
    clash = set(kinds_own) & set(counters)
    if clash:
        raise ValueError(f"the family's counters {sorted(clash)} carry names "
                         f"the traffic kind uses itself")
    return {name: len(v) if isinstance(v, list) else v
            for name, v in counters.items()}


def added_since(marks: Dict[str, Any], counters: Dict[str, Any]
                ) -> Dict[str, Any]:
    """What the window added to each of the family's running totals."""
    if set(marks) != set(counters):
        raise ValueError(f"the family's counters changed names inside the "
                         f"window: {sorted(set(marks) ^ set(counters))}")
    return {name: v[marks[name]:] if isinstance(v, list) else v - marks[name]
            for name, v in counters.items()}


def slowest(step_s: List[float], n: int = 3) -> List[Tuple[int, float]]:
    """(index, seconds) of the n slowest steps, slowest first."""
    order = sorted(range(len(step_s)), key=lambda i: -step_s[i])
    return [(i, step_s[i]) for i in order[:n]]


def percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: List[float]) -> float:
    return statistics.median(values)
