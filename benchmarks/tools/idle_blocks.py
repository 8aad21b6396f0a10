"""Name the long idle gaps of the device in a traced serve window, and count
the long host turns in a recorder's ring.

    python3 benchmarks/tools/idle_blocks.py --xplane <file.xplane.pb>
    python3 benchmarks/tools/idle_blocks.py --ring <flight-*.trace.json>
    python3 benchmarks/tools/idle_blocks.py --workload <cell> --seed <n> \
        --seconds 30 --trace <0|1>

``--xplane``: for every gap of ``--least-ms`` (10) or more between the
operations of the first device inside ``bench.window``: its offset from the
window's start, its length, the host span it stands under (innermost first,
as ``lib/trace.reduce`` names gaps, the one that took most of it), and how
much of it a garbage collection (``hvd.host.gc.*``), a ``serve.see``, a
``wait.copy`` and a ``wait.ready`` overlap.

``--ring``: a flight recording (``tracing.dump_flight_recording``) holds
``serve.cycle`` with ``cpu_ms`` and ``gc_ms``; every cycle whose host turn
(wall less the ``engine.decode.wait`` beneath it) is ``--turn-ms`` (20) or
more is printed with its wall, CPU, collection and wait milliseconds: wall
less CPU less wait is the time the thread stood off its core.

``--workload``: on the chip, one run of the cell as ``benchmarks/run.py``
makes it, the profiler's rows kept for the first report and, where the
recorder is on (``HOROVOD_TRACE=1``; give ``HOROVOD_TRACE_BUFFER_SPANS``
room for the window), the ring's own rows for the second. One JSON line on
standard output and in ``chiprun_out/idle_blocks/<cell>.jsonl``.

``--workload`` with ``--windows N``: one engine, its warm rotation, then N
traced windows one after the other, each opened and closed as the kind
does it (``lib/window.measured``, ``bench.window``) and read at once: a run
of the benchmark spends two minutes on one window, most of them on reading
every operation back, and a block of 10 ms lies between two programs, so
here the device's ``XLA Modules`` line stands for its operations. A
second thread writes a mark into the trace every 5 ms: where a block holds
its marks the process ran and the device (or what launches its programs)
stood still; where the marks stop for the block's length the whole
process did. One JSON line a window."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import cell as cells, stalls, trace    # noqa: E402

# what may stand over a block, by the reading of ISSUE 37 it speaks for
KINDS = {"gc": stalls.GC, "see": stalls.SEE, "copy": stalls.COPY,
         "ready": stalls.READY}
WINDOW_MARK = "bench.window."       # + start / end: instants in the ring
BEAT, BEAT_S = "idle_blocks.beat", 0.005    # the second thread's mark
# the ring has the program's names bare, the profiler with ``hvd.`` before them
RING_CYCLE, RING_WAIT = stalls.CYCLE[4:], stalls.WAIT[4:]
RING_GC, RING_READY = stalls.GC[4:], stalls.READY[4:]
STATS = ("host_turn_ms_p50", "host_turn_ms_max_over_p50", "see_ms_p50",
         "host_gc_ms_per_s", "host_gc_pause_ms_max",
         "decode_wait_copy_ms_p50", "decode_wait_copy_ms_max")


# -- the profiler's rows -------------------------------------------------------

def _shares(gap: trace.Interval,
            host: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of the gap by host span, innermost first: a later-starting
    span takes what it covers, an enclosing one what is left."""
    left, got = [gap], {}
    for name, lo, hi in sorted(host, key=lambda s: -s[1]):
        if not left:
            break
        if hi <= gap[0] or lo >= gap[1]:
            continue
        uncovered = trace.subtract(left, [(lo, hi)])
        took = trace.total(left) - trace.total(uncovered)
        if took > 0:
            got[name] = got.get(name, 0.0) + took
            left = uncovered
    if left:
        got["_no_span_"] = trace.total(left)
    return got


def blocks(rows: List[Dict[str, Any]], least_s: float = 0.010,
           line: str = trace.OPS_LINE) -> List[Dict[str, Any]]:
    """The first device's idle gaps of ``least_s`` or more inside the
    window, by offset; between the events of ``line``."""
    windows = [r for r in rows if r["name"] == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN!r} span in the "
                         f"trace, found {len(windows)}")
    w_lo = windows[0]["start_ns"] * 1e-9
    w_hi = w_lo + windows[0]["dur_ns"] * 1e-9
    planes = sorted({r["plane"] for r in rows
                     if r["plane"].startswith("/device:TPU:")
                     and r["line"] == line})
    if not planes:
        raise ValueError("no operation ran on a device in this trace")
    ops, host = [], []
    for r in rows:
        lo = r["start_ns"] * 1e-9
        hi = lo + r["dur_ns"] * 1e-9
        if r["plane"] == planes[0] and r["line"] == line:
            ops.append((lo, hi))
        elif (r["name"].startswith(trace.SPAN_PREFIXES)
              and r["name"] != trace.WINDOW_SPAN):
            host.append((r["name"], lo, hi))
    cycles = sorted((lo, hi) for name, lo, hi in host if name == stalls.CYCLE
                    and w_lo <= lo < w_hi)
    out = []
    for gap in trace.subtract([(w_lo, w_hi)], trace.clip(ops, w_lo, w_hi)):
        if gap[1] - gap[0] < least_s:
            continue
        near = [s for s in host if s[2] > gap[0] and s[1] < gap[1]]
        shares = _shares(gap, near)
        over = [trace.overlap([gap], [c]) for c in cycles]
        cycle = max(range(len(over)), key=over.__getitem__) if over else None
        out.append({
            "offset_s": gap[0] - w_lo, "seconds": gap[1] - gap[0],
            "under": max(shares, key=shares.get),
            "shares": dict(sorted(shares.items(), key=lambda kv: -kv[1])[:4]),
            "overlaps": {
                kind: trace.overlap([gap], [(lo, hi) for name, lo, hi in near
                                            if name.startswith(prefix)])
                for kind, prefix in KINDS.items()},
            # which of the window's cycles it fell in, and that cycle's wall
            "cycle": cycle,
            "cycle_s": (cycles[cycle][1] - cycles[cycle][0]
                        if cycle is not None and over[cycle] > 0 else None)})
    return out


# -- the recorder's ring -------------------------------------------------------

def _window(events: List[Dict[str, Any]]) -> Tuple[float, float]:
    """The window's two marks on the ring's clock, where the list holds
    them (a run of this tool); all of it where it does not."""
    marks = {e["name"]: e["ts"] for e in events
             if e.get("name", "").startswith(WINDOW_MARK)}   # the last pair
    return (marks.get(WINDOW_MARK + "start", float("-inf")),
            marks.get(WINDOW_MARK + "end", float("inf")))


def _beneath(spans: Dict[int, Dict[str, Any]], name: str
             ) -> Dict[int, float]:
    """Milliseconds of the spans of that name by the cycle above them."""
    out: Dict[int, float] = {}
    for e in spans.values():
        if e["name"] != name:
            continue
        up = spans.get(e["args"].get("parent_id"))
        while up is not None and up["name"] != RING_CYCLE:
            up = spans.get(up["args"].get("parent_id"))
        if up is not None:
            sid = up["args"]["span_id"]
            out[sid] = out.get(sid, 0.0) + e["dur"] * 1e-3
    return out


def ring_cycles(events: List[Dict[str, Any]]) -> List[Dict[str, float]]:
    """Every ``serve.cycle`` of a Chrome-trace event list inside the window
    as wall, CPU, collection and wait milliseconds (and how much of the wait
    was for the device, ``.ready``), in order."""
    lo, hi = _window(events)
    spans = {e["args"]["span_id"]: e for e in events if e.get("ph") == "X"}
    waited, ready = _beneath(spans, RING_WAIT), _beneath(spans, RING_READY)
    out = []
    for sid, e in sorted(spans.items()):
        if e["name"] != RING_CYCLE or not lo <= e["ts"] < hi:
            continue
        a = e["args"]
        wall, wait = e["dur"] * 1e-3, waited.get(sid, 0.0)
        out.append({"cycle": a.get("cycle"), "ts_ms": (e["ts"] - lo) * 1e-3
                    if lo > float("-inf") else e["ts"] * 1e-3,
                    "wall_ms": wall, "cpu_ms": a.get("cpu_ms"),
                    "gc_ms": a.get("gc_ms"), "wait_ms": wait,
                    "ready_ms": ready.get(sid, 0.0),
                    "turn_ms": wall - wait})
    return out


def ring_report(events: List[Dict[str, Any]], turn_ms: float,
                wait_over_ms: float = 50.0) -> Dict[str, Any]:
    """The window's cycles and collections: the cycles with a long host
    turn, and those whose wait for the device is ``wait_over_ms`` above the
    median wait (the device, or the thread that feeds it, stood still with
    a step queued). The collection the harness makes as it opens the window
    stands apart (it is before the first cycle)."""
    lo, hi = _window(events)
    cycles = ring_cycles(events)
    first = min((e["ts"] for e in events if e.get("name") == RING_CYCLE
                 and lo <= e["ts"] < hi), default=lo)
    turns = sorted(c["turn_ms"] for c in cycles)
    waits = sorted(c["wait_ms"] for c in cycles)
    gcs: Dict[str, List[float]] = {}
    opening = 0.0
    for e in events:
        if not e.get("name", "").startswith(RING_GC) \
                or not lo <= e["ts"] < hi:
            continue
        if e["ts"] < first:
            opening += e["dur"] * 1e-3
        else:
            gcs.setdefault(e["name"], []).append(e["dur"] * 1e-3)
    return {
        "cycles": len(cycles),
        "turn_ms_p50": turns[len(turns) // 2] if turns else None,
        "turn_ms_max": turns[-1] if turns else None,
        "long_turns": [c for c in cycles if c["turn_ms"] >= turn_ms],
        "wait_ms_p50": waits[len(waits) // 2] if waits else None,
        "long_waits": [c for c in cycles if waits and c["wait_ms"]
                       >= waits[len(waits) // 2] + wait_over_ms],
        "window_open_collect_ms": opening,
        "collections": {name: {"count": len(ms), "total_ms": sum(ms),
                               "max_ms": max(ms)}
                        for name, ms in sorted(gcs.items())}}


# -- one run of a cell, on the chip --------------------------------------------

def _marked_log():
    """A compile log that also puts the window's two ends into the ring as
    instants: ``lib/window.measured`` marks them there, before it collects
    and after the last cycle."""
    from benchmarks.lib import chip
    from horovod_tpu import tracing as recorder

    class MarkedLog(chip.CompileLog):
        def mark_window(self, on: bool) -> None:
            super().mark_window(on)
            recorder.instant(WINDOW_MARK + ("start" if on else "end"))

    return MarkedLog()


def _add_ring(row: Dict[str, Any], turn_ms: float) -> None:
    """Where the recorder is on: the ring's report of the window just
    closed, and under each block the cycle it fell in as the ring has it
    (the window's cycles in order)."""
    from horovod_tpu import tracing as recorder
    from horovod_tpu.tracing import spans as recorder_spans
    row["recorder"] = recorder.enabled()
    if not recorder.enabled():
        return
    events = recorder_spans.chrome_events(recorder.snapshot())
    row["ring"] = ring_report(events, turn_ms)
    row["ring"]["dropped"] = recorder.summary()["dropped"]
    in_window = ring_cycles(events)
    for b in row.get("blocks", []):
        if b["cycle"] is not None and b["cycle"] < len(in_window):
            b["ring_cycle"] = in_window[b["cycle"]]


def run_cell(args) -> Dict[str, Any]:
    from benchmarks.lib import chip, report
    cell = cells.load_cell(args.workload)
    chip.place_compile_cache()
    devices = chip.take_chips(cell.chips)
    kept: List[List[Dict[str, Any]]] = []
    load = trace.load_xplane

    def keeping(path: str) -> List[Dict[str, Any]]:
        kept.append(load(path))     # ``Session.reduce`` deletes the file
        return kept[-1]

    trace.load_xplane = keeping
    try:
        kind = importlib.import_module(
            "benchmarks.kinds." + cell.traffic["kind"])
        rec = kind.run(cell, args.seed, args.seconds, args.trace, devices,
                       time.perf_counter(), _marked_log())
    finally:
        trace.load_xplane = load
    row: Dict[str, Any] = {
        "cell": cell.name, "seed": args.seed, "trace": args.trace,
        "window_s": rec.elapsed_s,
        "correct": bool(rec.correct), "failed": rec.failed,
        "serve_out_tokens_per_s":
            rec.end_to_end.get("serve_out_tokens_per_s")}
    if rec.trace is not None:
        row["idle_share"] = 1.0 - rec.trace.busy_s / rec.trace.window_s
        row["idle_gaps"] = rec.trace.breakdown()["idle_gaps"][:4]
        row["metrics"] = {name: report.read_metric(name, rec)
                          for name in STATS}
        row["blocks"] = blocks(kept[-1], args.least_ms * 1e-3)
    _add_ring(row, args.turn_ms)
    return row


def _light_rows(path: str) -> List[Dict[str, Any]]:
    """``load_xplane``'s rows less the devices' operations."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name != trace.MODULES_LINE:
                continue
            for e in line.events:
                if (device or e.name.startswith(trace.SPAN_PREFIXES)
                        or e.name == BEAT):
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": e.name, "start_ns": e.start_ns,
                                 "dur_ns": e.duration_ns})
    return rows


def beats_over(rows: List[Dict[str, Any]], block: Dict[str, Any],
               w_lo_ns: int) -> Dict[str, Any]:
    """The second thread's marks from 20 ms before a block to 20 ms after
    it: how many, and the longest silence between two of them, the last
    mark before that stretch and the first after it counted in."""
    lo = w_lo_ns * 1e-9 + block["offset_s"] - 0.020
    hi = lo + block["seconds"] + 0.040
    at = sorted(r["start_ns"] * 1e-9 for r in rows if r["name"] == BEAT)
    near = [t for t in at if lo <= t <= hi]
    around = ([t for t in at if t < lo][-1:] + near
              + [t for t in at if t > hi][:1])
    return {"marks": len(near),
            "longest_silence_s": max(
                (b - a for a, b in zip(around, around[1:])), default=None)}


def hunt(args) -> int:
    """``--windows N``: see the module's text."""
    import glob
    import shutil
    import threading

    import jax

    from benchmarks.lib import chip, window
    cell = cells.load_cell(args.workload)
    chip.place_compile_cache()
    devices = chip.take_chips(cell.chips)
    traffic = cell.traffic
    kind = importlib.import_module("benchmarks.kinds." + traffic["kind"])
    family = importlib.import_module(
        "benchmarks.families." + cell.config["family"])

    compile_log = _marked_log()
    spans = window.Spans(annotate=True)
    prog = family.ServeProgram(cell.config, traffic, args.seed, devices,
                               spans)
    sched = prog.scheduler
    clients = kind.Clients(traffic, prog.vocab, args.seed)
    owner: Dict[int, int] = {}          # rid -> client
    served = set()
    n_done = 0

    def submit(client: int) -> None:
        prompt, n_out = clients.next(client)
        req = prog.request(len(owner), prompt, n_out)
        req.arrival = time.perf_counter()
        owner[req.rid] = client
        sched.submit(req)

    def cycle() -> None:
        nonlocal n_done
        with spans.span("bench.schedule"):
            sched.step()
        done, n_done = sched.completed[n_done:], len(sched.completed)
        for req in done:
            served.add(owner[req.rid])
            submit(owner[req.rid])

    for c in range(traffic["clients"]):
        submit(c)
    while len(served) < traffic["clients"]:
        cycle()                         # the warm rotation
    seconds = window.length(args.seconds, traffic, 1)
    out = os.path.join(cells.ROOT, "chiprun_out", "idle_blocks")
    os.makedirs(out, exist_ok=True)

    def beat() -> None:
        while True:
            with jax.profiler.TraceAnnotation(BEAT):
                pass
            time.sleep(BEAT_S)

    threading.Thread(target=beat, daemon=True).start()
    for w in range(args.windows):
        session = trace.Session(cell.name, f"{args.seed}-w{w}")
        t_open = time.perf_counter()
        with window.measured(compile_log, session), \
                spans.span("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                cycle()
            elapsed = time.perf_counter() - t0
        (path,) = glob.glob(os.path.join(session.dir, "**", "*.xplane.pb"),
                            recursive=True)
        rows = _light_rows(path)
        shutil.rmtree(session.dir, ignore_errors=True)
        found = blocks(rows, args.least_ms * 1e-3, line=trace.MODULES_LINE)
        (w_row,) = [r for r in rows if r["name"] == trace.WINDOW_SPAN]
        for b in found:
            b["beats"] = beats_over(rows, b, w_row["start_ns"])
        device = [r for r in rows if r["line"] == trace.MODULES_LINE]
        row: Dict[str, Any] = {
            "cell": cell.name, "seed": args.seed, "window": w,
            "window_s": elapsed,
            "opening_s": t0 - t_open,   # the session's start, the collection
            "first_program_s": (min(r["start_ns"] for r in device)
                                - w_row["start_ns"]) * 1e-9,
            "beats": sum(1 for r in rows if r["name"] == BEAT),
            "blocks": found}
        _add_ring(row, args.turn_ms)
        if "ring" in row:
            row["ring"]["long_turns"] = row["ring"]["long_turns"][:6]
        line = json.dumps(row)
        with open(os.path.join(
                out, f"{cell.name}.{args.seed}.windows.jsonl"), "a") as f:
            f.write(line + "\n")
        print(line, flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--xplane")
    ap.add_argument("--ring")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2_500_000_017)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--windows", type=int, default=0)
    ap.add_argument("--least-ms", type=float, default=10.0)
    ap.add_argument("--turn-ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    if sum(x is not None for x in (args.xplane, args.ring,
                                   args.workload)) != 1:
        ap.error("give one of --xplane, --ring and --workload")
    if args.xplane:
        for b in blocks(trace.load_xplane(args.xplane), args.least_ms * 1e-3):
            print(json.dumps(b))
        return 0
    if args.ring:
        with open(args.ring) as f:
            print(json.dumps(ring_report(json.load(f)["traceEvents"],
                                         args.turn_ms)))
        return 0
    if args.windows:
        return hunt(args)
    row = run_cell(args)
    out = os.path.join(cells.ROOT, "chiprun_out", "idle_blocks")
    os.makedirs(out, exist_ok=True)
    line = json.dumps(row)
    with open(os.path.join(out, args.workload + ".jsonl"), "a") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
