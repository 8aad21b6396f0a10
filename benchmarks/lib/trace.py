"""From a profiler trace to busy time, kernel time, exposed collective time
and a breakdown. The reduction works on plain event rows
``{"plane", "line", "name", "start_ns", "dur_ns"}`` so that it can be
checked on a small recorded trace; ``load_xplane`` makes such rows from the
``.xplane.pb`` the JAX profiler writes (``jax.profiler.ProfileData``).

What is a device: a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per executed operation, containers (``while``, ``call``,
``conditional``) enclosing their children. Busy time is the union of those
events; an operation's own time is its duration less its children's. The
benchmark's host spans (``bench.*``) are the ``TraceAnnotation`` events on the
host planes, on the same clock; the window is the ``bench.window`` span."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.lib import report

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "call", "conditional")
# a collective by its HLO opcode or by the JAX primitive the compiler named
# the instruction after: the gradient all-reduce of ``sync_gradients`` is
# ``psum.<n>`` in the trace of the four-chip cell (my chip run, PR 24)
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|pmean|pmax|pmin|ppermute|all_gather|all_to_all|reduce_scatter")
_SUFFIX_RE = re.compile(r"(\.\d+)+$")


# -- interval algebra ---------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out: List[Interval] = []
    b = union(b)
    for lo, hi in union(a):
        cur = lo
        for blo, bhi in b:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    return total(union(a)) - total(subtract(a, b))


# -- reduction ----------------------------------------------------------------

def op_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%all-reduce.5 = ...`` -> its name."""
    name = name.strip().lstrip("%").split(" ")[0]
    return _SUFFIX_RE.sub("", name)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                           # mean over the devices
    devices: int
    op_self_s: Dict[str, float]             # own time by operation, 1st device
    op_total_s: Dict[str, float]            # full duration by operation, mean
    op_calls: Dict[str, int]                # events by operation, 1st device
    exposed_collective_s: float             # mean over the devices
    idle_gaps: List[Tuple[str, float]]      # by host span, 1st device
    # every run of a compiled program on the first device:
    # (program name, seconds inside the window, names of the operations in it)
    programs: List[Tuple[str, float, frozenset]] = dataclasses.field(
        default_factory=list)

    @property
    def module_total_s(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, seconds, _ in self.programs:
            out[name] = out.get(name, 0.0) + seconds
        return out

    def module_s(self, part: str = "", lacking: str = None
                 ) -> Optional[float]:
        """Device seconds of the runs of compiled programs whose name
        contains ``part``, first device; None where there is none. The serving
        engine's programs carry no name in the trace (``jit__unknown``), so
        a program can also be told by what it runs:
        ``lacking`` keeps the runs that hold no operation of that name, and
        gives None unless some other run in the window does hold one."""
        hit = [(seconds, any(lacking in op for op in ops) if lacking else False)
               for name, seconds, ops in self.programs if part in name]
        if lacking and not any(has for _, has in hit):
            return None
        hit = [seconds for seconds, has in hit if not has]
        return sum(hit) if hit else None

    def kernel_s(self, kernel: str) -> Optional[float]:
        """Device seconds of the events whose name contains ``kernel``, mean
        over the devices; None where there is none."""
        hit = [v for k, v in self.op_total_s.items() if kernel in k]
        return sum(hit) if hit else None

    def kernel_calls(self, kernel: str) -> int:
        return sum(v for k, v in self.op_calls.items() if kernel in k)

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Own time per name: duration less the children's, by enclosure."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []         # [end, name, own]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for lo, hi, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(lo)
        if stack:
            stack[-1][2] -= hi - lo
        stack.append([hi, name, hi - lo])
    close(float("inf"))
    return out


def reduce(rows: List[Dict[str, Any]]) -> Summary:
    windows = [r for r in rows if r["name"] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                         f"found {len(windows)}")
    w_lo = windows[0]["start_ns"] * 1e-9
    w_hi = w_lo + windows[0]["dur_ns"] * 1e-9
    planes: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[str, float, float]] = []
    modules: Dict[str, List[Tuple[float, float, str]]] = {}
    for r in rows:
        lo = r["start_ns"] * 1e-9
        hi = lo + r["dur_ns"] * 1e-9
        if r["plane"].startswith("/device:TPU:") and r["line"] == MODULES_LINE:
            if min(hi, w_hi) > max(lo, w_lo):
                modules.setdefault(r["plane"], []).append(
                    (max(lo, w_lo), min(hi, w_hi), r["name"].split("(")[0]))
        elif r["plane"].startswith("/device:TPU:") and r["line"] == OPS_LINE:
            if hi > w_lo and lo < w_hi:
                planes.setdefault(r["plane"], []).append(
                    (max(lo, w_lo), min(hi, w_hi), op_name(r["name"])))
        elif r["name"].startswith("bench.") and r["name"] != WINDOW_SPAN:
            host.append((r["name"], lo, hi))
    if not planes:
        raise ValueError("no operation ran on a device inside the window")
    names = sorted(planes)
    busy, exposed = [], []
    op_total: Dict[str, float] = {}
    for p in names:
        ev = planes[p]
        busy.append(total(union((lo, hi) for lo, hi, _ in ev)))
        coll = [(lo, hi) for lo, hi, n in ev if COLLECTIVE_RE.search(n)]
        comp = [(lo, hi) for lo, hi, n in ev
                if not COLLECTIVE_RE.search(n) and n not in CONTAINERS]
        exposed.append(total(subtract(coll, comp)))
        for lo, hi, n in ev:
            op_total[n] = op_total.get(n, 0.0) + (hi - lo) / len(names)
    first = planes[names[0]]
    calls: Dict[str, int] = {}
    for _, _, n in first:
        calls[n] = calls.get(n, 0) + 1
    gaps = subtract([(w_lo, w_hi)], [(lo, hi) for lo, hi, _ in first])
    by_span: Dict[str, float] = {}
    for g in gaps:
        left = [g]
        # innermost first: a later-starting span is the more specific one
        for name, lo, hi in sorted(host, key=lambda s: -s[1]):
            got = total(left) - total(subtract(left, [(lo, hi)]))
            if got > 0:
                by_span[name] = by_span.get(name, 0.0) + got
                left = subtract(left, [(lo, hi)])
        rest = total(left)
        if rest > 0:
            by_span["_no_span_"] = by_span.get("_no_span_", 0.0) + rest
    starts = sorted((lo, n) for lo, _, n in first)
    keys = [lo for lo, _ in starts]
    programs = [
        (name, hi - lo, frozenset(
            n for _, n in starts[bisect.bisect_left(keys, lo):
                                 bisect.bisect_left(keys, hi)]))
        for lo, hi, name in modules.get(names[0], [])]
    return Summary(
        window_s=w_hi - w_lo, busy_s=sum(busy) / len(busy),
        devices=len(names), op_self_s=_self_times(first),
        op_total_s=op_total, op_calls=calls,
        exposed_collective_s=sum(exposed) / len(exposed),
        idle_gaps=sorted(by_span.items(), key=lambda kv: -kv[1]),
        programs=programs)



# -- the profiler -------------------------------------------------------------

def load_xplane(path: str) -> List[Dict[str, Any]]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith("bench."):
                    continue
                rows.append({"plane": plane.name, "line": line.name,
                             "name": e.name, "start_ns": e.start_ns,
                             "dur_ns": e.duration_ns})
    return rows


class Session:
    """One profiler trace, written under the cell's output directory, read
    back into event rows and deleted: a trace is large and the host keeps
    every block once written."""

    def __init__(self, cell_name: str, seed: int):
        self.dir = os.path.join(report.out_dir(cell_name), f"trace-{seed}")
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self) -> Summary:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file under {self.dir}, "
                               f"found {files}")
        rows = load_xplane(files[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        summary = reduce(rows)
        print(f"benchmark: traced programs (device seconds) "
              f"{ {k: round(v, 4) for k, v in summary.module_total_s.items()} }",
              file=sys.stderr)
        return summary
