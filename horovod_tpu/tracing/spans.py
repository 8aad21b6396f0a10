"""Span recording: the distributed-tracing core.

One process-global ring buffer of completed spans (a bounded
``collections.deque`` — appends are GIL-atomic, the oldest spans fall off
at capacity, so a long run's recorder is O(HOROVOD_TRACE_BUFFER_SPANS)
memory forever). Every span carries the run's trace id, its own span id,
and the id of the span that was open on the same thread when it started
(parent links — the causal chain negotiate → fuse → dispatch → wait is a
tree, not a flat list).

Two sinks, one recorder. A recorded span is also a
``jax.profiler.TraceAnnotation`` named ``"hvd." + name``, so it lands on
the host plane of any profiler trace on the profiler's clock, beside the
device's ``XLA Ops``. With the recorder off but a JAX profiler session
active, ``span()`` hands back that bare annotation: no ring, no state,
nothing exported at shutdown. This module is the one place that talks
to the profiler.

The interpreter's garbage collections are spans too (``host.gc.gen0`` /
``gen1`` / ``gen2``, from one ``gc.callbacks`` hook that ``enable()`` and
``init_from_env()`` put in once): a pause of the host thread, and an idle
gap of the device over it, carries the collector's name on either sink.
With both sinks off the hook returns at its first line.

The OFF path is the contract: ``span()`` with ``HOROVOD_TRACE=0`` and no
profiler session returns a module-level no-op context-manager singleton
— no object, dict, or tuple is allocated, and the only cost is one
attribute read, one ``is-falsy`` branch and one call of the profiler's
static ``is_enabled()`` (benchmarked in tests/test_tracing.py). Call
sites on per-entry hot paths should guard attribute-dict construction
with ``enabled()``.

Timestamps are ``time.perf_counter()`` microseconds relative to a
process epoch captured at ``enable()``; the epoch's wall-clock value
(``epoch_unix``) travels with every export so the cross-controller
merge (tracing/merge.py) can shift hosts onto one timeline.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import socket
import threading
import time
from collections import Counter, deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from horovod_tpu.config import knobs
from horovod_tpu.utils.logging import get_logger

logger = get_logger("horovod_tpu.tracing")

# Span categories used by the built-in instrumentation (free-form strings;
# these constants exist so the classifier/tests and docs agree on names).
CAT_COORDINATOR = "coordinator"
CAT_WAIT = "wait"
CAT_CHECKPOINT = "checkpoint"
CAT_PREEMPTION = "preemption"
CAT_ELASTIC = "elastic"
CAT_DATA = "data"
CAT_TRAIN = "train"
CAT_TIMELINE = "timeline"
CAT_SERVE = "serve"
CAT_HOST = "host"

# Prefix of every span's name on the profiler's host plane.
PROFILER_PREFIX = "hvd."
_profiling = TraceAnnotation.is_enabled


class _State:
    """Mutable recorder state. ``enabled`` is read unlocked on the hot
    path (a GIL-atomic bool); everything else is touched under ``lock``
    or is itself atomic (deque.append, itertools.count)."""

    __slots__ = ("enabled", "buffer", "capacity", "trace_id", "epoch_perf",
                 "epoch_unix", "lock", "open_async", "open_spans",
                 "dropped", "gc_open", "gc_us")

    def __init__(self):
        self.enabled = False
        self.capacity = 0
        self.buffer: "deque" = deque(maxlen=1)
        self.trace_id = ""
        self.epoch_perf = time.perf_counter()
        self.epoch_unix = time.time()
        self.lock = threading.Lock()
        # (name, cat) -> (start_us, span_id, parent_id): cross-thread
        # begin/end pairs (the timeline's QUEUE phase starts on the
        # enqueuing thread and ends on the cycle thread).
        self.open_async: Dict[Any, Any] = {}
        # span_id -> (name, cat, start_us, tid, parent_id, attrs) for
        # spans currently inside their `with` body — the flight
        # recording must ship the STUCK operation, which by definition
        # has not exited yet (GIL-atomic dict set/pop, no lock).
        self.open_spans: Dict[int, Any] = {}
        self.dropped = 0
        # the collection under way, (annotation, start_us): the
        # interpreter runs one at a time and calls the hook under its
        # lock; and the microseconds of all that ended with the recorder on
        self.gc_open: Any = None
        self.gc_us = 0.0


_state = _State()
_span_ids = itertools.count(1)
_tls = threading.local()


def _now_us() -> float:
    return (time.perf_counter() - _state.epoch_perf) * 1e6


def enabled() -> bool:
    """Whether spans are currently being recorded (hot-path guard for
    attribute-dict construction at call sites)."""
    return _state.enabled


def active() -> bool:
    """Whether a :func:`span` opened now goes anywhere: the recorder or a
    JAX profiler session is on. Guards work done only to be timed (the
    engine's split of a readback into wait and copy)."""
    return _state.enabled or _profiling()


def enable(buffer_spans: Optional[int] = None,
           trace_id: Optional[str] = None) -> None:
    """Turn the recorder on (idempotent). A fresh trace id is minted
    unless one is passed (the launcher can export a shared id so every
    host's spans join one logical trace)."""
    _install_gc_hook()
    with _state.lock:
        if _state.enabled:
            return
        cap = int(buffer_spans
                  if buffer_spans is not None
                  else knobs.get("HOROVOD_TRACE_BUFFER_SPANS"))
        cap = max(cap, 16)
        _state.capacity = cap
        _state.buffer = deque(maxlen=cap)
        _state.trace_id = trace_id or os.urandom(8).hex()
        _state.epoch_perf = time.perf_counter()
        _state.epoch_unix = time.time()
        _state.open_async.clear()
        _state.open_spans.clear()
        _state.dropped = 0
        _state.enabled = True
    logger.info("tracing enabled (trace_id=%s, ring buffer=%d spans)",
                _state.trace_id, cap)


def disable() -> None:
    with _state.lock:
        _state.enabled = False


def reset() -> None:
    """Drop recorded spans and disable (test isolation)."""
    with _state.lock:
        _state.enabled = False
        _state.buffer = deque(maxlen=max(_state.capacity, 1) or 1)
        _state.open_async.clear()
        _state.open_spans.clear()


def init_from_env() -> None:
    """HOROVOD_TRACE=1 enables the recorder at hvd.init(), and where a
    ServeEngine is built in a process that never calls it. HVD_TRACE_ID
    (minted by `hvdrun --trace`) joins every host's spans into one
    logical trace. The collector's hook goes in either way, so that a
    profiler session over a process with the recorder off names its
    pauses too."""
    _install_gc_hook()
    if knobs.get("HOROVOD_TRACE"):
        enable(trace_id=os.environ.get("HVD_TRACE_ID"))


def trace_id() -> str:
    return _state.trace_id


def epoch_unix() -> float:
    """Wall-clock value of the perf epoch spans are relative to."""
    return _state.epoch_unix


class _NoopSpan:
    """The OFF path: one shared instance, allocation-free enter/exit."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    """A live span: records (start, duration, parent) into the ring
    buffer at exit, and brackets the same interval on the profiler's
    host plane. Allocated only when tracing is enabled."""

    __slots__ = ("name", "cat", "attrs", "_t0", "_id", "_parent", "_ann")

    def __init__(self, name: str, cat: str, attrs: Optional[Dict]):
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def __enter__(self):
        self._ann = annotation(self.name)
        self._ann.__enter__()
        self._t0 = _now_us()
        self._id = next(_span_ids)
        self._parent = getattr(_tls, "span_id", 0)
        _tls.span_id = self._id
        _state.open_spans[self._id] = (
            self.name, self.cat, self._t0, threading.get_ident(),
            self._parent, self.attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        _tls.span_id = self._parent
        _state.open_spans.pop(self._id, None)
        record(self.name, self.cat, self._t0, _now_us() - self._t0,
               attrs=self.attrs, span_id=self._id, parent_id=self._parent)
        self._ann.__exit__(exc_type, exc, tb)
        return False


def annotation(name: str):
    """``"hvd." + name`` on the profiler's host plane while a JAX
    profiler session is active, the shared no-op otherwise. For an
    interval that is to show in a device trace and not in the ring (the
    timeline's natively covered spans); everything else uses
    :func:`span`."""
    if not _profiling():
        return _NOOP
    return TraceAnnotation(PROFILER_PREFIX + name)


def span(name: str, cat: str = "runtime",
         attrs: Optional[Dict] = None):
    """``with trace.span("coordinator.cycle", cat=..., attrs={...}):`` —
    the instrumentation primitive. Recorder on: ring buffer + profiler
    annotation. Recorder off but a JAX profiler session active: the
    bare annotation (so any ``jax.profiler`` trace holds the program's
    spans with no knob set). Neither: the shared no-op (zero allocation;
    see module docstring). NEVER use inside a jit/pjit/shard_map-traced
    body — it would measure trace time, not run time (hvdlint HVD206);
    label device ops with ``jax.named_scope`` there instead."""
    if _state.enabled:
        return _Span(name, cat, attrs)
    return annotation(name)


def record(name: str, cat: str, start_us: float, dur_us: float,
           attrs: Optional[Dict] = None, span_id: Optional[int] = None,
           parent_id: int = 0, tid: Optional[int] = None) -> int:
    """Append one completed span (used by _Span and by adapters that
    already measured elsewhere — e.g. the timeline mirror). Returns the
    span's id (0 while the recorder is off), for children recorded the
    same way to name as their parent."""
    if not _state.enabled:
        return 0
    buf = _state.buffer
    if len(buf) >= _state.capacity:
        # maxlen discards the oldest silently; count it so summary()'s
        # `dropped` is honest (racy += may undercount — diagnostic only).
        _state.dropped += 1
    sid = span_id if span_id is not None else next(_span_ids)
    buf.append((
        name, cat, float(start_us), float(dur_us),
        tid if tid is not None else threading.get_ident(),
        sid, parent_id, attrs or None))
    return sid


def record_interval(name: str, cat: str, t0: float, t1: float,
                    attrs: Optional[Dict] = None,
                    parent_id: int = 0) -> int:
    """Append a span for an interval that is already over, given as two
    ``time.perf_counter()`` readings (a request's arrival and finish);
    returns its id like :func:`record`. Ring only: the profiler takes
    no span after the fact."""
    return record(name, cat, (t0 - _state.epoch_perf) * 1e6,
                  max(t1 - t0, 0.0) * 1e6, attrs=attrs,
                  parent_id=parent_id)


def instant(name: str, cat: str = "runtime",
            attrs: Optional[Dict] = None) -> None:
    """Zero-duration marker."""
    if not _state.enabled:
        return
    record(name, cat, _now_us(), 0.0, attrs=attrs)


# -- cross-thread begin/end pairs (timeline QUEUE/NEGOTIATE mirroring) ------

def begin_async(name: str, cat: str) -> None:
    if not _state.enabled:
        return
    with _state.lock:
        _state.open_async[(name, cat)] = (
            _now_us(), next(_span_ids), getattr(_tls, "span_id", 0))


def end_async(name: str, cat: str, attrs: Optional[Dict] = None) -> None:
    if not _state.enabled:
        return
    with _state.lock:
        opened = _state.open_async.pop((name, cat), None)
    if opened is None:
        return
    t0, sid, parent = opened
    record(name, cat, t0, _now_us() - t0, attrs=attrs, span_id=sid,
           parent_id=parent)


# -- the interpreter's garbage collections ----------------------------------

_GC_SPANS = ("host.gc.gen0", "host.gc.gen1", "host.gc.gen2")


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` hook: every collection is a span
    ``host.gc.gen<n>`` on the trace's clock, under the span open on the
    thread that ran into it, so that a pause of the host (and an idle gap
    of the device over it) carries the collector's name."""
    if not _state.enabled and not _profiling():
        return
    name = _GC_SPANS[info["generation"]]
    if phase == "start":
        ann = annotation(name)
        ann.__enter__()
        _state.gc_open = (ann, _now_us())
        return
    opened, _state.gc_open = _state.gc_open, None
    if opened is None:              # turned on in the middle of one
        return
    ann, t0 = opened
    dur = _now_us() - t0
    ann.__exit__(None, None, None)
    if _state.enabled:
        _state.gc_us += dur
        record(name, CAT_HOST, t0, dur,
               attrs={"collected": info["collected"],
                      "uncollectable": info["uncollectable"]},
               parent_id=getattr(_tls, "span_id", 0))


def _install_gc_hook() -> None:
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_us() -> float:
    """Microseconds of the collections that ended with the recorder on,
    all told: a caller takes the difference across its own interval
    (``serve.cycle``'s ``gc_ms``)."""
    return _state.gc_us


# -- reads / export ---------------------------------------------------------

def _buffer_copy() -> List[Any]:
    """Copy the ring buffer while other threads may be appending.
    ``list(deque)`` is a single C call (GIL held throughout in CPython),
    but that is an implementation detail — retry on the RuntimeError a
    mutated-during-iteration deque would raise elsewhere."""
    for _ in range(8):
        try:
            return list(_state.buffer)
        except RuntimeError:
            continue
    return []


def snapshot() -> List[Dict[str, Any]]:
    """The ring buffer as plain dicts (oldest first)."""
    rows = []
    for name, cat, ts, dur, tid, sid, parent, attrs in _buffer_copy():
        row = {"name": name, "cat": cat, "ts_us": ts, "dur_us": dur,
               "tid": tid, "span_id": sid, "parent_id": parent}
        if attrs:
            row["attrs"] = attrs
        rows.append(row)
    return rows


def open_span_rows() -> List[Dict[str, Any]]:
    """Spans currently in flight (``with`` bodies not yet exited and
    unmatched ``begin_async`` pairs) as snapshot-shaped rows, duration
    measured up to now and tagged ``in_flight`` — the part of a flight
    recording that explains a stall."""
    now = _now_us()
    rows: List[Dict[str, Any]] = []
    for sid, (name, cat, t0, tid, parent, attrs) in list(
            _state.open_spans.items()):
        a = dict(attrs or {})
        a["in_flight"] = True
        rows.append({"name": name, "cat": cat, "ts_us": t0,
                     "dur_us": now - t0, "tid": tid, "span_id": sid,
                     "parent_id": parent, "attrs": a})
    with _state.lock:
        open_async = list(_state.open_async.items())
    for (name, cat), (t0, sid, parent) in open_async:
        rows.append({"name": name, "cat": cat, "ts_us": t0,
                     "dur_us": now - t0, "tid": 0, "span_id": sid,
                     "parent_id": parent, "attrs": {"in_flight": True}})
    return rows


def span_counts() -> Dict[str, int]:
    """Span count per category (the TRACE.json / CI-smoke summary)."""
    return dict(Counter(s[1] for s in _buffer_copy()))


def summary(process_index: int = 0) -> Dict[str, Any]:
    """Everything a peer needs to merge this process's spans onto its
    own timeline: spans + the perf-epoch's wall-clock anchor."""
    return {
        "process_index": int(process_index),
        "hostname": socket.gethostname(),
        "pid": os.getpid(),
        "trace_id": _state.trace_id,
        "epoch_unix": _state.epoch_unix,
        "dropped": int(_state.dropped),
        "spans": snapshot(),
    }


def chrome_events(spans: List[Dict[str, Any]], pid: int = 0,
                  shift_us: float = 0.0,
                  trace_id_: str = "") -> List[Dict[str, Any]]:
    """Chrome trace-events (complete ``ph:"X"`` form) for a span list."""
    evs: List[Dict[str, Any]] = []
    for s in spans:
        args = dict(s.get("attrs") or {})
        args["span_id"] = s["span_id"]
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        if trace_id_:
            args["trace_id"] = trace_id_
        evs.append({"ph": "X", "name": s["name"], "cat": s["cat"],
                    "pid": pid, "tid": s["tid"],
                    "ts": s["ts_us"] + shift_us, "dur": s["dur_us"],
                    "args": args})
    return evs


def write_chrome_trace(path: str,
                       events: List[Dict[str, Any]],
                       metadata: Optional[Dict[str, Any]] = None) -> str:
    """Atomic Chrome-trace/Perfetto JSON write (tmp + rename — a scraper
    or a crashed exporter can never leave a torn file)."""
    payload = {"displayTimeUnit": "ms",
               "metadata": metadata or {},
               "traceEvents": events}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def trace_dir() -> str:
    """Directory for trace artifacts (flight recordings, exports):
    HOROVOD_TRACE_DIR, defaulting to ``.hvdtrace`` under CWD."""
    return knobs.get("HOROVOD_TRACE_DIR") or ".hvdtrace"


def dump_flight_recording(reason: str,
                          directory: Optional[str] = None) -> Optional[str]:
    """Write the last-N spans ring buffer to the trace dir — called from
    the stall-inspector abort and preemption paths so every stall/abort
    ships its own flight recording. Returns the path, or None when
    tracing never recorded anything (nothing to ship). Never raises:
    this runs on failure paths that must stay failable-safe."""
    try:
        spans_ = snapshot() + open_span_rows()
        if not spans_:
            return None
        d = directory or trace_dir()
        os.makedirs(d, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_" else "-"
                       for c in reason)[:64]
        path = os.path.join(
            d, f"flight-{safe}-pid{os.getpid()}.trace.json")
        evs: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": 0,
             "args": {"name": socket.gethostname()}}]
        evs += chrome_events(spans_, trace_id_=_state.trace_id)
        write_chrome_trace(path, evs, metadata={
            "reason": reason, "trace_id": _state.trace_id,
            "epoch_unix": _state.epoch_unix, "wall_time": time.time()})
        from horovod_tpu import metrics as M
        M.counter("hvd_trace_flight_dumps_total",
                  "Flight recordings written on stall/abort paths").inc()
        logger.warning("flight recording (%s): %d spans -> %s",
                       reason, len(spans_), path)
        return path
    except Exception:
        logger.warning("flight recording for %r failed", reason,
                       exc_info=True)
        return None
