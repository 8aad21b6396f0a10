"""The serve loop's host pauses: each of the seven readers of ISSUE 37 on
hand-made spans with numbers worked by hand, what they give without a trace
and on a program that lacks the spans, and ``tools/idle_blocks.py`` on
hand-made profiler rows and ring events."""

import pytest

from benchmarks.lib import report, stalls, trace
from benchmarks.tools import idle_blocks
from test_bench_spec import _made_up_run

SERVE = "pythia410m_serve_closed"
READERS = ("host_turn_ms_p50", "host_turn_ms_max_over_p50", "see_ms_p50",
           "host_gc_ms_per_s", "host_gc_pause_ms_max",
           "decode_wait_copy_ms_p50", "decode_wait_copy_ms_max")
S = trace.Span


def _cycle(start, seconds, wait=0.003, copy=0.0002, see=0.0004, gc=()):
    """One cycle as the program writes it: (name, start, seconds, parent
    relative to the cycle's own index)."""
    rows = [(stalls.CYCLE, start, seconds, None),
            ("hvd.serve.decode", start + 0.0005, seconds - 0.0006, 0),
            ("hvd.engine.decode.dispatch", start + 0.0006, 0.001, 1),
            (stalls.WAIT, start + 0.0016, wait, 1),
            (stalls.READY, start + 0.0016, wait - copy, 3),
            (stalls.COPY, start + 0.0016 + wait - copy, copy, 3),
            (stalls.SEE, start + 0.0017 + wait, see, 1)]
    rows += [(stalls.GC + f"gen{gen}", start + 0.0018 + wait, s, 6)
             for gen, s in gc]
    return rows


def _run_with(cycles):
    rec = _made_up_run(SERVE)
    rec.trace.window_s = 2.0
    rec.trace.spans = []
    for rows in cycles:
        base = len(rec.trace.spans)
        rec.trace.spans += [S(name, start, seconds,
                              -1 if up is None else base + up)
                            for name, start, seconds, up in rows]
    return rec


def _four_cycles():
    """Three cycles of 5 ms and one of 120, a 3 ms wait beneath each; the
    long one held a full collection of 100 ms, two others a young one."""
    return _run_with([
        _cycle(0.10, 0.005, copy=0.0002, see=0.0004, gc=[(0, 0.0003)]),
        _cycle(0.20, 0.005, copy=0.0003, see=0.0005),
        _cycle(0.30, 0.120, copy=0.0010, see=0.1010, gc=[(2, 0.1)]),
        _cycle(0.50, 0.005, copy=0.0004, see=0.0006, gc=[(0, 0.0005)])])


@pytest.mark.parametrize("metric, want", [
    # turns of 2, 2, 117 and 2 ms
    ("host_turn_ms_p50", 2.0),
    ("host_turn_ms_max_over_p50", 58.5),
    # 0.4, 0.5, 101 and 0.6 ms
    ("see_ms_p50", 0.55),
    # 0.3 + 100 + 0.5 ms of collections in a window of 2 s
    ("host_gc_ms_per_s", 50.4),
    ("host_gc_pause_ms_max", 100.0),
    # 0.2, 0.3, 1.0 and 0.4 ms
    ("decode_wait_copy_ms_p50", 0.35),
    ("decode_wait_copy_ms_max", 1.0),
])
def test_reader_by_hand(metric, want):
    assert report.read_metric(metric, _four_cycles()) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_no_trace_no_reading(metric):
    rec = _four_cycles()
    rec.trace = None
    assert report.read_metric(metric, rec) is None


@pytest.mark.parametrize("metric", READERS)
def test_the_example_makes_the_reader_read(metric):
    rec = _made_up_run(SERVE)
    rec.trace.spans = []
    assert report.read_metric(metric, rec) is None
    report.load_reader(metric)[0].example(rec)
    assert report.read_metric(metric, rec) is not None


def test_a_wait_counts_for_the_cycle_it_stands_under_and_no_other():
    rec = _four_cycles()
    # a drain after the last cycle (``ServeScheduler.run``'s end): no cycle
    # above it, so no cycle's turn is shortened by it
    rec.trace.spans.append(S(stalls.WAIT, 0.60, 0.050, -1))
    assert stalls.host_turns(rec.trace.spans) == pytest.approx(
        [0.002, 0.002, 0.117, 0.002])
    # the halves of a wait are not taken beside it
    assert sum(1 for s in rec.trace.spans if s.name == stalls.READY) == 4


def test_a_window_without_a_collection_reads_zero_and_a_silent_program_nothing():
    quiet = _run_with([_cycle(0.1, 0.005), _cycle(0.2, 0.005)])
    assert report.read_metric("host_gc_ms_per_s", quiet) == 0.0
    assert report.read_metric("host_gc_pause_ms_max", quiet) is None
    # the parent's spans: cycles and waits, no ``serve.see``, no hook
    old = _made_up_run(SERVE)
    assert report.read_metric("host_gc_ms_per_s", old) is None
    assert report.read_metric("host_gc_pause_ms_max", old) is None
    assert report.read_metric("see_ms_p50", old) is None
    assert report.read_metric("decode_wait_copy_ms_max", old) is None
    # its cycles and waits are there: 50 - 30 and 40 - 34 ms
    assert report.read_metric("host_turn_ms_p50", old) == pytest.approx(13.0)


# -- tools/idle_blocks.py -----------------------------------------------------

NS = 1e-9
DEVICE, HOST = "/device:TPU:0", "/host:CPU"


def _row(plane, line, name, start_s, seconds):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(round(start_s / NS)),
            "dur_ns": int(round(seconds / NS))}


def _rows():
    """A window of 4 s from t = 10: the device runs 5 ms operations back to
    back but for 100 ms at the window's start and 100 ms from 2 s in."""
    rows = [_row(HOST, "main", trace.WINDOW_SPAN, 10.0, 4.0)]
    t = 10.1
    while t < 14.0 - 1e-9:
        if not 12.0 - 1e-9 <= t < 12.1 - 1e-9:
            rows.append(_row(DEVICE, trace.OPS_LINE, "fusion.1", t, 0.005))
        t += 0.005
    rows.append(_row("/device:TPU:1", trace.OPS_LINE, "fusion.1", 10.0, 0.01))
    host = [(stalls.CYCLE, 9.99, 0.125), ("hvd.serve.decode", 9.995, 0.119),
            (stalls.CYCLE, 11.98, 0.13), ("hvd.serve.decode", 11.985, 0.124),
            (stalls.SEE, 11.99, 0.115), (stalls.GC + "gen2", 11.995, 0.108),
            (stalls.CYCLE, 13.0, 0.006), (stalls.WAIT, 13.001, 0.004),
            (stalls.READY, 13.001, 0.003), (stalls.COPY, 13.004, 0.001)]
    return rows + [_row(HOST, "main", *h) for h in host]


def test_blocks_on_hand_made_rows():
    first, second = idle_blocks.blocks(_rows())
    assert first["offset_s"] == pytest.approx(0.0)
    assert first["seconds"] == pytest.approx(0.1)
    assert first["under"] == "hvd.serve.decode"
    assert first["overlaps"] == {"gc": 0.0, "see": 0.0, "copy": 0.0,
                                 "ready": 0.0}
    # the cycle it stands in began before the window: none of the window's
    assert first["cycle_s"] is None
    assert second["offset_s"] == pytest.approx(2.0)
    assert second["seconds"] == pytest.approx(0.1)
    assert second["under"] == "hvd.host.gc.gen2"
    assert second["shares"]["hvd.host.gc.gen2"] == pytest.approx(0.1)
    assert second["overlaps"]["gc"] == pytest.approx(0.1)
    assert second["overlaps"]["see"] == pytest.approx(0.1)
    assert second["overlaps"]["copy"] == second["overlaps"]["ready"] == 0.0
    assert (second["cycle"], second["cycle_s"]) == (0, pytest.approx(0.13))
    # a gap under the least length is no block; a lower bar finds none more
    assert len(idle_blocks.blocks(_rows(), least_s=0.2)) == 0
    assert len(idle_blocks.blocks(_rows(), least_s=0.001)) == 2


def test_the_second_threads_marks_over_a_block():
    rows = _rows()
    w_lo_ns = rows[0]["start_ns"]
    # a mark every 5 ms all through the second block, none from 10.01 to
    # 10.09 s in the first: there the whole process stood still
    t = 9.9
    while t < 12.2:
        if not 10.01 < t < 10.09:
            rows.append(_row(HOST, "beat", idle_blocks.BEAT, t, 1e-6))
        t += 0.005
    first, second = idle_blocks.blocks(rows)
    ran = idle_blocks.beats_over(rows, second, w_lo_ns)
    assert ran["marks"] == 28
    assert ran["longest_silence_s"] == pytest.approx(0.005)
    stood = idle_blocks.beats_over(rows, first, w_lo_ns)
    assert stood["longest_silence_s"] == pytest.approx(0.085)
    # marks that stop before a block and start again past 20 ms after it:
    # the silence reaches to the first one after
    late = [r for r in rows if not (r["name"] == idle_blocks.BEAT
                                    and 11.99 < r["start_ns"] * NS < 12.14)]
    gone = idle_blocks.beats_over(late, second, w_lo_ns)
    assert gone["marks"] <= 3
    assert gone["longest_silence_s"] == pytest.approx(0.155, abs=0.006)
    # the marks are no host span: a block is not named by them
    assert first["under"] == "hvd.serve.decode"


def test_blocks_refuse_a_trace_without_a_window_or_a_device():
    rows = [r for r in _rows() if r["name"] != trace.WINDOW_SPAN]
    with pytest.raises(ValueError, match="bench.window"):
        idle_blocks.blocks(rows)
    rows = [r for r in _rows() if not r["plane"].startswith("/device")]
    with pytest.raises(ValueError, match="no operation"):
        idle_blocks.blocks(rows)


def _event(name, ts_ms, dur_ms, sid, parent=0, **attrs):
    return {"ph": "X", "name": name, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "args": {"span_id": sid, "parent_id": parent, **attrs}}


def test_ring_cycles_on_hand_made_events():
    events = [
        {"ph": "M", "name": "process_name", "pid": 0, "args": {}},
        _event("serve.cycle", 0, 5, 1, cycle=7, cpu_ms=1.9, gc_ms=0.0),
        _event("bench.window.start", 10, 0, 2),
        _event("host.gc.gen2", 11, 80, 3),          # the harness's own
        _event("serve.cycle", 100, 5, 4, cycle=8, cpu_ms=1.9, gc_ms=0.0),
        _event("serve.decode", 100.5, 4, 5, parent=4),
        _event("engine.decode.wait", 101, 3, 6, parent=5),
        _event("engine.decode.wait.ready", 101, 2.9, 7, parent=6),
        _event("serve.cycle", 110, 120, 8, cycle=9, cpu_ms=12.0, gc_ms=0.4),
        _event("serve.decode", 110.5, 119, 9, parent=8),
        _event("engine.decode.wait", 111, 3, 10, parent=9),
        _event("host.gc.gen0", 115, 0.4, 11, parent=9),
        _event("bench.window.end", 300, 0, 12),
        _event("serve.cycle", 310, 5, 13, cycle=10, cpu_ms=2.0, gc_ms=0.0)]
    short, long_ = idle_blocks.ring_cycles(events)
    assert (short["cycle"], long_["cycle"]) == (8, 9)
    assert short["turn_ms"] == pytest.approx(2.0)
    assert long_["turn_ms"] == pytest.approx(117.0)
    assert long_["ts_ms"] == pytest.approx(100.0)       # from the mark
    # 120 of wall, 12 on the core, 3 waiting: 105 ms the thread was off it
    assert long_["wall_ms"] - long_["cpu_ms"] - long_["wait_ms"] == \
        pytest.approx(105.0)
    got = idle_blocks.ring_report(events, turn_ms=20.0)
    assert got["cycles"] == 2 and got["long_turns"] == [long_]
    # both waited 3 ms, 2.9 of the first one's for the device
    assert got["wait_ms_p50"] == pytest.approx(3.0)
    assert got["long_waits"] == [] and short["ready_ms"] == pytest.approx(2.9)
    # a wait of 100 ms over the median's: the device stood with a step queued
    stalled = events + [
        _event("serve.cycle", 240, 110, 20, cycle=10, cpu_ms=2.0, gc_ms=0.0),
        _event("engine.decode.wait", 241, 108, 21, parent=20),
        _event("engine.decode.wait.ready", 241, 107.5, 22, parent=21)]
    (waited,) = idle_blocks.ring_report(stalled, turn_ms=20.0)["long_waits"]
    assert (waited["cycle"], waited["turn_ms"]) == (10, pytest.approx(2.0))
    assert waited["ready_ms"] == pytest.approx(107.5)
    assert got["window_open_collect_ms"] == pytest.approx(80.0)
    assert got["collections"] == {"host.gc.gen0": {
        "count": 1, "total_ms": pytest.approx(0.4),
        "max_ms": pytest.approx(0.4)}}
    # a flight recording of a server has no marks: every cycle counts
    bare = [e for e in events if not e["name"].startswith("bench.")]
    assert len(idle_blocks.ring_cycles(bare)) == 4
