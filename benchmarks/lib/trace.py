"""From a profiler trace to busy time, kernel time, exposed collective time
and a breakdown. The reduction works on plain event rows
``{"plane", "line", "name", "start_ns", "dur_ns"}`` so that it can be
checked on a small recorded trace; ``load_xplane`` makes such rows from the
``.xplane.pb`` the JAX profiler writes (``jax.profiler.ProfileData``).

What is a device: a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per executed operation, containers (``while``, ``call``,
``conditional``) enclosing their children. Busy time is the union of those
events; an operation's own time is its duration less its children's. The
benchmark's host spans (``bench.*``) and the program's own (``hvd.*``) are the
``TraceAnnotation`` events on the host planes, on the same clock; the window
is the ``bench.window`` span.

A scope is a ``jax.named_scope`` of the program whose name starts with
``hvd_``. An ``XLA Ops`` event carries no ``op_name`` (jax 0.9.0), so the
scope is found through the compiled text of the program that ran: event name
= instruction name -> its ``metadata={op_name="..."}`` -> the ``hvd_*``
components of that path. Instruction names repeat from program to program, so
an event is looked up in the text of the program whose run holds it."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import itertools
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
SPAN_PREFIXES = ("bench.", "hvd.")
UNSCOPED = "_unscoped_"
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# one component of an op_name path that is a scope, bare or inside the
# wrappers of a transformation (``transpose(jvp(hvd_attention))``); the name
# of the jitted function (``jit(hvd_serve_decode)``) is no scope
_SCOPE_RE = re.compile(r"^((?:\w+\()*)(hvd_\w+)\)*$")


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


# -- names -------------------------------------------------------------------

def instruction_name(name: str) -> str:
    """``%fusion.123 = f32[] ...`` -> ``fusion.123``: an event's name as the
    compiled text has it."""
    return name.strip().lstrip("%").split(" ")[0]


def op_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%all-reduce.5 = ...`` -> its name."""
    return _SUFFIX_RE.sub("", instruction_name(name))


# -- scopes, from a program's compiled text -----------------------------------

def scope_path(op_name_path: str) -> str:
    """The ``hvd_*`` components of an ``op_name``, outer to inner, joined by
    ``/``: ``jit(step)/hvd_optimizer/hvd_unfused_apply/mul`` ->
    ``hvd_optimizer/hvd_unfused_apply``; ``""`` where there is none."""
    found = []
    for part in op_name_path.split("/"):
        m = _SCOPE_RE.match(part)
        if m and "jit(" not in m.group(1):
            found.append(m.group(2))
    return "/".join(found)


@dataclasses.dataclass
class ProgramText:
    """What the reduction keeps of one compiled program's text."""
    module: str                     # ``jit_hvd_serve_decode``
    scope_of: Dict[str, str]        # every instruction -> its scope path


def parse_program_text(hlo_text: str) -> ProgramText:
    lines = hlo_text.splitlines()
    head = _MODULE_RE.match(lines[0]) if lines else None
    if head is None:
        raise ValueError("a compiled text starts with 'HloModule <name>'; "
                         f"this one with {hlo_text[:60]!r}")
    scope_of: Dict[str, str] = {}
    for line in lines[1:]:
        m = _INSTRUCTION_RE.match(line)
        if m:
            found = _OP_NAME_RE.search(line)
            scope_of[m.group(1)] = scope_path(found.group(1)) if found else ""
    return ProgramText(head.group(1), scope_of)


def _text_of_run(texts: List[ProgramText], names: frozenset
                 ) -> Optional[ProgramText]:
    """The text a run belongs to, among those of its module name: the one
    that holds most of the run's instruction names (an engine compiles one
    prefill program per bucket under one name)."""
    if len(texts) <= 1:
        return texts[0] if texts else None
    return max(texts, key=lambda t: sum(1 for n in names if n in t.scope_of))


# -- reduction ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Span:
    """One host span that started inside the window."""
    name: str
    start_s: float                  # from the window's start
    seconds: float
    parent: int                     # index of the span that encloses it, or -1


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
    # the benchmark's and the program's host spans, by start, nesting kept
    spans: List[Span] = dataclasses.field(default_factory=list)
    # own device seconds on the first device: program name -> scope path ->
    # operation -> seconds; only of programs whose compiled text was given.
    # Time under no ``hvd_*`` scope stands under ``UNSCOPED``.
    scope_op_s: Dict[str, Dict[str, Dict[str, float]]] = dataclasses.field(
        default_factory=dict)

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

    def span_seconds(self, name: str) -> List[float]:
        return [s.seconds for s in self.spans if s.name == name]

    def span_self_seconds(self, name: str) -> List[float]:
        """Each such span's seconds less those of the spans directly inside
        it."""
        inside: Dict[int, float] = {}
        for s in self.spans:
            if s.parent >= 0:
                inside[s.parent] = inside.get(s.parent, 0.0) + s.seconds
        return [s.seconds - inside.get(i, 0.0)
                for i, s in enumerate(self.spans) if s.name == name]

    def program_runs(self, part: str) -> int:
        return sum(1 for name, _, _ in self.programs if part in name)

    def scope_seconds(self, program: str, scopes: Sequence[str]
                      ) -> Optional[Dict[str, float]]:
        """Own device seconds, first device, of the programs whose name
        contains ``program``, split over ``scopes``: a scope path counts for
        the innermost of its components that ``scopes`` names, and for
        ``"other"`` where it names none of them (time under no scope with
        it). None where no such program's text was given."""
        hit = [by_scope for name, by_scope in self.scope_op_s.items()
               if program in name]
        if not hit:
            return None
        out = {scope: 0.0 for scope in (*scopes, "other")}
        for by_scope in hit:
            for path, ops in by_scope.items():
                mine = [c for c in path.split("/") if c in scopes]
                out[mine[-1] if mine else "other"] += sum(ops.values())
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def _own_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, str, float]]:
    """(start, name, own time) of each event: its duration less its
    children's, by enclosure."""
    out: List[Tuple[float, str, float]] = []
    stack: List[List[Any]] = []         # [end, start, name, own]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, lo, name, own = stack.pop()
            out.append((lo, name, max(own, 0.0)))

    for lo, hi, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(lo)
        if stack:
            stack[-1][3] -= hi - lo
        stack.append([hi, lo, name, hi - lo])
    close(float("inf"))
    return out


def _nested(host: List[Tuple[str, float, float, str]], w_lo: float,
            w_hi: float) -> List[Span]:
    """The host spans that start inside the window, by start; a span's parent
    is the one that encloses it on the same thread."""
    inside = sorted((s for s in host if w_lo <= s[1] < w_hi),
                    key=lambda s: (s[1], -s[2]))
    spans: List[Span] = []
    open_on: Dict[str, List[Tuple[float, int]]] = {}    # thread -> (end, idx)
    for name, lo, hi, thread in inside:
        stack = open_on.setdefault(thread, [])
        while stack and stack[-1][0] <= lo:
            stack.pop()
        spans.append(Span(name, lo - w_lo, hi - lo,
                          stack[-1][1] if stack else -1))
        stack.append((hi, len(spans) - 1))
    return spans


def reduce(rows: List[Dict[str, Any]],
           hlo_texts: Optional[Dict[str, str]] = None) -> Summary:
    """``hlo_texts``: the compiled text of every program the window ran, by
    any label; device time is told by scope for the programs among them."""
    windows = [r for r in rows if r["name"] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                         f"found {len(windows)}")
    w_lo = windows[0]["start_ns"] * 1e-9
    w_hi = w_lo + windows[0]["dur_ns"] * 1e-9
    planes: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[str, float, float, str]] = []
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
                    (max(lo, w_lo), min(hi, w_hi),
                     instruction_name(r["name"])))
        elif r["name"].startswith(SPAN_PREFIXES) and r["name"] != WINDOW_SPAN:
            host.append((r["name"], lo, hi, f"{r['plane']}|{r['line']}"))
    if not planes:
        raise ValueError("no operation ran on a device inside the window")
    names = sorted(planes)
    # ``fusion.123`` -> ``fusion``, worked out once a name: a window holds
    # hundreds of thousands of events under a few hundred names
    short = {n: op_name(n) for ev in planes.values() for _, _, n in ev}
    busy, exposed = [], []
    op_total: Dict[str, float] = {}
    for p in names:
        ev = [(lo, hi, short[n]) for lo, hi, n in planes[p]]
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
        calls[short[n]] = calls.get(short[n], 0) + 1
    gaps = subtract([(w_lo, w_hi)], [(lo, hi) for lo, hi, _ in first])
    by_span: Dict[str, float] = {}
    # innermost first: a later-starting span is the more specific one
    by_start = sorted(host, key=lambda s: -s[1])
    neg_starts = [-s[1] for s in by_start]
    # how far a span or any that starts before it reaches: where that is not
    # into the gap, no span further down the list touches the gap either
    reach = list(itertools.accumulate((s[2] for s in reversed(by_start)), max))
    reach.reverse()
    for g in gaps:
        left = [g]
        for i in range(bisect.bisect_right(neg_starts, -g[1]), len(by_start)):
            if not left or reach[i] <= g[0]:
                break
            name, lo, hi, _ = by_start[i]
            uncovered = subtract(left, [(lo, hi)])
            got = total(left) - total(uncovered)
            if got > 0:
                by_span[name] = by_span.get(name, 0.0) + got
                left = uncovered
        rest = total(left)
        if rest > 0:
            by_span["_no_span_"] = by_span.get("_no_span_", 0.0) + rest
    own = sorted(_own_times(first))
    keys = [lo for lo, _, _ in own]
    op_self: Dict[str, float] = {}
    for _, n, seconds in own:
        op_self[short[n]] = op_self.get(short[n], 0.0) + seconds
    texts: Dict[str, List[ProgramText]] = {}
    for text in (hlo_texts or {}).values():
        parsed = parse_program_text(text)
        texts.setdefault(parsed.module, []).append(parsed)
    programs = []
    scope_op_s: Dict[str, Dict[str, Dict[str, float]]] = {}
    for lo, hi, name in modules.get(names[0], []):
        held = own[bisect.bisect_left(keys, lo):bisect.bisect_left(keys, hi)]
        programs.append((name, hi - lo,
                         frozenset(short[n] for _, n, _ in held)))
        text = _text_of_run(texts.get(name, []),
                            frozenset(n for _, n, _ in held))
        if text is None:
            continue
        by_scope = scope_op_s.setdefault(name, {})
        for _, n, seconds in held:
            ops = by_scope.setdefault(text.scope_of.get(n) or UNSCOPED, {})
            ops[short[n]] = ops.get(short[n], 0.0) + seconds
    return Summary(
        window_s=w_hi - w_lo, busy_s=sum(busy) / len(busy),
        devices=len(names), op_self_s=op_self,
        op_total_s=op_total, op_calls=calls,
        exposed_collective_s=sum(exposed) / len(exposed),
        idle_gaps=sorted(by_span.items(), key=lambda kv: -kv[1]),
        programs=programs, spans=_nested(host, w_lo, w_hi),
        scope_op_s=scope_op_s)



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
                if not device and not e.name.startswith(SPAN_PREFIXES):
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

    def reduce(self, hlo_texts: Optional[Dict[str, str]] = None) -> Summary:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file under {self.dir}, "
                               f"found {files}")
        rows = load_xplane(files[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        summary = reduce(rows, hlo_texts)
        print(f"benchmark: traced programs (device seconds) "
              f"{ {k: round(v, 4) for k, v in summary.module_total_s.items()} }",
              file=sys.stderr)
        for program, by_scope in summary.scope_op_s.items():
            for path, ops in sorted(by_scope.items(),
                                    key=lambda kv: -sum(kv[1].values())):
                top = sorted(ops.items(), key=lambda kv: -kv[1])[:4]
                print(f"benchmark: {program} under {path}: "
                      f"{sum(ops.values()):.4f} s ("
                      + ", ".join(f"{k} {v:.4f}" for k, v in top) + ")",
                      file=sys.stderr)
        return summary
