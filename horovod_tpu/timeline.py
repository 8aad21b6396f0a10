"""Chrome-trace timeline (ref common/timeline.{h,cc}).

The reference's coordinator writes a chrome://tracing JSON of every tensor's
lifecycle — NEGOTIATE phases, QUEUE, fusion-buffer memcpys, the backend op,
callback — from a dedicated writer thread fed by lock-free queues
(timeline.h:28, timeline.cc:150,298), toggled by ``HOROVOD_TIMELINE[=DYNAMIC]``
and ``horovod_start/stop_timeline`` (operations.cc:1073-1105).

TPU translation: host-side phases (queue, fusion planning, dispatch, handle
wait) are recorded here in the same Chrome trace format; device-side spans
come from XLA via ``jax.profiler`` — every span is also an ``hvd.<name>``
annotation on the profiler's host plane (written by tracing/spans.py) so
the xplane trace and this host trace align by name. A dedicated writer
thread drains a queue, as in the reference.

Rebuilt on the tracing subsystem (horovod_tpu/tracing/): timeline events
mirror into the span ring buffer by default, so Horovod-style
NEGOTIATE/ALLREDUCE phase tracing and the framework's own spans land in
ONE exported trace (the merged Perfetto file) — pass ``mirror=False`` at
call sites that already emit native spans for the same interval (the
coordinator and the eager wait do). Two writer-format guarantees:

- the Python writer emits spec-compliant COMPLETE events (``ph:"X"`` with
  ``dur``) for ``span()`` intervals instead of paired B/E (the native C++
  writer keeps B/E pairs — its emitter has no dur slot);
- the file is a valid JSON array after EVERY flush (each event write
  re-seals the array close), so a mid-run process death can never leave
  an unparseable timeline.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

from horovod_tpu.config import knobs


def _spans():
    """The tracing span recorder (lazy import keeps module init light)."""
    from horovod_tpu.tracing import spans
    return spans


# Per-thread count of open mirror=False timeline spans: their intervals
# are natively covered, so nested timeline spans must not mirror either
# (see Timeline.span).
_mirror_tls = threading.local()


# Phase names mirroring ref common.h:79-113 activity strings
NEGOTIATE = "NEGOTIATE"
QUEUE = "QUEUE"
FUSION = "MEMCPY_IN_FUSION_BUFFER"
DISPATCH = "DISPATCH"
WAIT = "WAIT_FOR_DATA"
CYCLE = "CYCLE"


class Timeline:
    """Per-process timeline writer. Thread-safe; events flow to a dedicated
    writer — the native C++ writer thread (csrc/core.cc TimelineWriter, the
    reference TimelineWriter timeline.cc:150 analogue) when built, else a
    Python queue + thread fallback with identical output format."""

    def __init__(self):
        self._queue: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._file = None
        self._tail = 0            # file offset of the array close bracket
        self._wrote_any = False
        self._native = None
        self._active = False
        # RLock: start() emits its own first event while holding the lock,
        # and _emit must hold it too (the native handle is freed by stop();
        # an unlocked read would race into a use-after-free).
        self._lock = threading.RLock()
        self._t0 = time.perf_counter()

    # -- lifecycle (ref horovod_start/stop_timeline operations.cc:1073) ------
    def start(self, path: str) -> None:
        with self._lock:
            if self._active:
                return
            from horovod_tpu import native
            if native.available():
                self._native = native.NativeTimelineWriter(
                    path, pid=os.getpid())
            else:
                # Valid from birth: "[\n]" parses as an empty array; every
                # event write seeks back over the close bracket, appends,
                # and re-seals — a kill -9 at any point leaves valid JSON.
                self._file = open(path, "w")
                self._file.write("[\n")
                self._tail = self._file.tell()
                self._file.write("]")
                self._file.flush()
                self._wrote_any = False
                self._thread = threading.Thread(target=self._writer_loop,
                                                daemon=True)
                self._thread.start()
            self._active = True
            self.instant("timeline_start")

    def stop(self) -> None:
        with self._lock:
            if not self._active:
                return
            self._active = False
            if self._native is not None:
                dropped = self._native.dropped
                if dropped:
                    # Bounded queue: a writer that fell behind dropped
                    # events (the unbounded Python fallback never does) —
                    # say so rather than hand over a silently gappy trace.
                    from horovod_tpu.utils.logging import get_logger
                    get_logger("horovod_tpu.timeline").warning(
                        "timeline dropped %d events (writer fell behind); "
                        "trace may have unmatched begin/end pairs", dropped)
                    self._native.event(
                        "timeline_dropped_events", "", "i", self._now_us(),
                        args_json=json.dumps({"dropped": dropped}))
                self._native.close(self._now_us())
                self._native = None
                return
            self._queue.put(None)
        if self._thread:
            self._thread.join(timeout=5)
            # Clear the dead thread: a start() after this stop() must spawn
            # a fresh writer, not observe (and trust) the joined one.
            self._thread = None
        with self._lock:
            if self._file:
                # The array is already sealed (every event write closed
                # it); append the end marker through the same re-seal.
                ev = {"name": "timeline_end", "ph": "i",
                      "ts": self._now_us(), "pid": os.getpid()}
                self._file.seek(self._tail)
                if self._wrote_any:
                    self._file.write(",\n")
                self._file.write(json.dumps(ev) + "\n]")
                self._file.truncate()
                self._file.close()
                self._file = None

    @property
    def active(self) -> bool:
        return self._active

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _writer_loop(self) -> None:
        while True:
            ev = self._queue.get()
            if ev is None:
                return
            try:
                with self._lock:
                    if self._file:
                        # Re-seal the array around every event: seek back
                        # over the close bracket, append, close again,
                        # flush. The file is loadable with json.loads
                        # after ANY event — a mid-run process death never
                        # leaves an unparseable trace (and per-event
                        # flush means nothing is lost in buffers).
                        # Record and bracket go out in ONE write: a
                        # tell() between them flushed the record alone,
                        # and a reader (or a death) just then found no
                        # bracket. The record is ASCII (json.dumps
                        # escapes the rest), so its length is its bytes.
                        record = (",\n" if self._wrote_any else "") \
                            + json.dumps(ev)
                        self._file.seek(self._tail)
                        self._file.write(record + "\n]")
                        self._file.truncate()
                        self._file.flush()
                        self._tail += len(record)
                        self._wrote_any = True
            except Exception:
                # A dying writer thread must not be silent: the trace
                # just went gappy (disk full, closed fd) — say so once
                # per event and keep draining so stop() can join us.
                from horovod_tpu import metrics as M
                from horovod_tpu.utils.logging import get_logger
                M.counter("hvd_timeline_write_failures_total",
                          "Timeline events lost to writer errors").inc()
                get_logger("horovod_tpu.timeline").warning(
                    "timeline writer failed to record %r; trace will "
                    "have a gap", ev.get("name"), exc_info=True)

    def _emit(self, ev: Dict[str, Any]) -> None:
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            if self._native is not None:
                args = ev.get("args")
                self._native.event(
                    ev["name"], ev.get("cat", ""), ev["ph"], ev["ts"],
                    tid=ev.get("tid", 0),
                    args_json=json.dumps(args) if args else None)
                return
        ev.setdefault("pid", os.getpid())
        self._queue.put(ev)

    # -- event API -----------------------------------------------------------
    # ``mirror`` (default True) additionally records the event into the
    # tracing span ring buffer (horovod_tpu/tracing/spans.py) so
    # Horovod-style phase tracing lands in the ONE exported trace; call
    # sites that already emit a native span for the same interval (the
    # coordinator's QUEUE pair and dispatch, the eager wait) pass False
    # so a run with both enabled does not double-count those intervals.

    def begin(self, name: str, phase: str, tid: int = 0,
              mirror: bool = True) -> None:
        self._emit({"name": name, "cat": phase, "ph": "B",
                    "ts": self._now_us(), "tid": tid})
        if mirror:
            _spans().begin_async(name, phase)

    def end(self, name: str, phase: str, tid: int = 0,
            args: Optional[Dict] = None, mirror: bool = True) -> None:
        ev = {"name": name, "cat": phase, "ph": "E",
              "ts": self._now_us(), "tid": tid}
        if args:
            ev["args"] = args
        self._emit(ev)
        if mirror:
            _spans().end_async(name, phase, attrs=args)

    def instant(self, name: str, args: Optional[Dict] = None,
                mirror: bool = True) -> None:
        ev = {"name": name, "ph": "i", "ts": self._now_us(), "s": "p"}
        if args:
            ev["args"] = args
        self._emit(ev)
        if mirror:
            _spans().instant(name, cat="timeline", attrs=args)

    def mark_cycle(self, cycle_idx: int) -> None:
        if knobs.get("HOROVOD_TIMELINE_MARK_CYCLES"):
            # Cycle markers carry the goodput phase they landed in, so
            # the Perfetto view and the time-attribution accountant
            # agree on phase boundaries (a cycle inside step_compute
            # is overlap; one inside exposed_collective is the wait
            # the accountant charges) — 'untracked' when accounting
            # is off.
            from horovod_tpu.goodput import accountant as _goodput
            self.instant(CYCLE, {"cycle": cycle_idx,
                                 "phase": _goodput.current_phase()})

    @contextmanager
    def span(self, name: str, phase: str = DISPATCH, tid: int = 0,
             mirror: bool = True):
        """Host span + matching XLA xplane annotation so device traces align
        (the reference's NVTX-range analogue, nvtx_op_range.h). The Python
        writer records ONE spec-compliant complete event (``ph:"X"`` with
        ``dur``); the native writer has no dur slot and keeps B/E pairs.

        A ``mirror=False`` span marks its interval as natively covered,
        so timeline spans NESTED inside it do not mirror either — the
        coordinator's solo dispatch reaches the eager sync path, whose
        own DISPATCH span would otherwise double-represent the interval
        the coordinator already declared natively spanned."""
        t0 = self._now_us()
        if self._native is not None:
            self.begin(name, phase, tid, mirror=False)
        mirror_here = mirror and not getattr(_mirror_tls, "suppress", 0)
        # one naming on the profiler's host plane ("hvd.<name>"), written
        # by the recorder: the mirrored span carries it; a natively
        # covered interval gets the annotation alone
        sp = (_spans().span(name, cat=phase) if mirror_here
              else _spans().annotation(name))
        if not mirror:
            _mirror_tls.suppress = getattr(_mirror_tls, "suppress", 0) + 1
        try:
            with sp:
                yield
        finally:
            if not mirror:
                _mirror_tls.suppress -= 1
            if self._native is not None:
                self.end(name, phase, tid, mirror=False)
            else:
                self._emit({"name": name, "cat": phase, "ph": "X",
                            "ts": t0, "dur": self._now_us() - t0,
                            "tid": tid})


_timeline = Timeline()


def get_timeline() -> Timeline:
    return _timeline


def start_timeline(path: str) -> None:
    """Runtime toggle (ref operations.cc:1073 horovod_start_timeline)."""
    _timeline.start(path)


def stop_timeline() -> None:
    _timeline.stop()


def init_from_env() -> None:
    """HOROVOD_TIMELINE=path starts at init; =DYNAMIC waits for
    start_timeline() (ref operations.cc:546-560)."""
    cfg = knobs.get("HOROVOD_TIMELINE")
    if cfg and cfg != "DYNAMIC":
        _timeline.start(cfg)
