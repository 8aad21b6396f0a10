"""The program's own spans and scopes in the reduction, on a small recorded
trace with the compiled texts of the two programs that ran in it, and each
reader of a span or a scope on the made-up run, with numbers worked by hand."""

import json
import os

import pytest

import bench_toy
from benchmarks.lib import report, trace
from test_bench_spec import _made_up_run

NS = 1e-9
SERVE, TRAIN, DP4 = ("pythia410m_serve_closed", "pythia410m_train_1chip",
                     "pythia410m_train_dp4")


# -- each reader on the made-up run (SPANS and SCOPES of test_bench_spec) ----

@pytest.mark.parametrize("cell, metric, want", [
    # dispatch spans of 1.5 and 1.3 ms, waits of 30 and 34
    (SERVE, "decode_dispatch_ms_p50", 1.4),
    (SERVE, "decode_wait_ms_p50", 32.0),
    # cycle 1: 50 - (1 + 2 + 10 + 36.5) = 0.5 ms of its own; cycle 2:
    # 40 - 39 = 1; what lies deeper (bench.decode, engine.*) is its
    # children's, not taken twice
    (SERVE, "sched_self_ms_per_cycle", 0.75),
    # no chunk was dispatched in either cycle
    (SERVE, "prefill_chunks_per_cycle", None),
    # four runs of the decode program: 16 + 4, 28, 6 and 30 + 12 + 2 ms
    (SERVE, "scope_ms_per_decode_step.attention", 5.0),
    (SERVE, "scope_ms_per_decode_step.mlp", 7.0),
    (SERVE, "scope_ms_per_decode_step.kv_write", 1.5),
    (SERVE, "scope_ms_per_decode_step.other", 11.0),
    # four steps: the three flash kernels 350 ms, 300, 50, 40 under
    # hvd_optimizer/hvd_unfused_apply, 300 under no scope
    (TRAIN, "scope_ms_per_step.attention", 87.5),
    (TRAIN, "scope_ms_per_step.mlp", 75.0),
    (TRAIN, "scope_ms_per_step.loss", 12.5),
    (TRAIN, "scope_ms_per_step.optimizer", 10.0),
    (TRAIN, "scope_ms_per_step.other", 75.0),
    # nothing under hvd_grad_sync until the reader's example brings it
    (DP4, "scope_ms_per_step.grad_sync", None),
])
def test_reader_by_hand(cell, metric, want):
    got = report.read_metric(metric, _made_up_run(cell))
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("cell, metric, want", [
    # the example's two cycles and one chunk beside the two that are there
    (SERVE, "prefill_chunks_per_cycle", 0.25),
    # the example's 110 ms of psum over four steps
    (DP4, "scope_ms_per_step.grad_sync", 27.5),
])
def test_reader_after_its_example(cell, metric, want):
    rec = _made_up_run(cell)
    report.load_reader(metric)[0].example(rec)
    assert report.read_metric(metric, rec) == pytest.approx(want)


def test_the_parts_of_a_program_add_up_to_its_own_time():
    rec = _made_up_run(SERVE)
    parts = [report.read_metric("scope_ms_per_decode_step." + p, rec)
             for p in ("attention", "mlp", "kv_write", "other")]
    own = sum(sum(ops.values()) for ops in
              rec.trace.scope_op_s["jit_hvd_serve_decode"].values())
    assert sum(parts) == pytest.approx(1e3 * own / 4)
    # a scope that no part names goes to ``other``, never to the others
    rec.trace.scope_op_s["jit_hvd_serve_decode"]["hvd_new"] = {"fusion": 0.04}
    assert report.read_metric("scope_ms_per_decode_step.other", rec) == \
        pytest.approx(21.0)
    assert report.read_metric("scope_ms_per_decode_step.mlp", rec) == \
        pytest.approx(7.0)


def test_a_span_or_scope_reader_with_nothing_to_read_returns_nothing():
    rec = _made_up_run(SERVE)
    rec.trace.spans = []
    rec.trace.scope_op_s = {}       # no compiled text came with the trace
    for m in rec.cell.per_layer:
        if m["source"] == "program_span" or m["name"].startswith("scope_"):
            assert report.read_metric(m["name"], rec) is None, m["name"]
    rec.trace = None                # an untraced run
    assert report.read_metric("decode_wait_ms_p50", rec) is None
    assert report.read_metric("scope_ms_per_decode_step.mlp", rec) is None
    with pytest.raises(ValueError, match="no scope for the part"):
        report.read_metric("scope_ms_per_decode_step.nonesuch",
                           _made_up_run(SERVE))


# -- the reduction, on one recorded scheduling cycle --------------------------

def _data(name):
    with open(os.path.join(bench_toy.DATA, name)) as f:
        return f.read()


@pytest.fixture(scope="module")
def cycle():
    """``serve_cycle_trace.json`` (its note says what was recorded and what
    was cut) with the texts of the two programs that ran in it."""
    return trace.reduce(
        json.loads(_data("serve_cycle_trace.json"))["rows"],
        {"serve_decode": _data("decode_cycle.hlo.txt"),
         "serve_prefill_256": _data("prefill_cycle.hlo.txt")})


def _ns(seconds_by_name):
    return {k: round(v / NS) for k, v in seconds_by_name.items()}


def test_scope_path_of_an_op_name():
    path = trace.scope_path
    assert path("jit(hvd_serve_decode)/while/body/closed_call/hvd_mlp/"
                "dot_general") == "hvd_mlp"
    # the jitted function's own name is no scope
    assert path("jit(hvd_serve_decode)/dot_general") == ""
    assert path("jit(train_step)/shard_map/transpose(jvp(hvd_loss))/mul") \
        == "hvd_loss"
    assert path("jit(train_step)/hvd_optimizer/hvd_unfused_apply/add") == \
        "hvd_optimizer/hvd_unfused_apply"
    assert path("jit(hvd_serve_decode)/while/body/closed_call/hvd_attention/"
                "jit(flash_paged_decode)/hvd_paged_decode/pallas_call") == \
        "hvd_attention/hvd_paged_decode"
    assert path("") == ""


def test_program_text_gives_every_instruction_its_scope():
    decode = trace.parse_program_text(_data("decode_cycle.hlo.txt"))
    assert decode.module == "jit_hvd_serve_decode"
    assert decode.scope_of["fusion.157"] == "hvd_mlp"
    assert decode.scope_of["hvd_paged_decode.11"] == \
        "hvd_attention/hvd_paged_decode"
    # the cast of a weight stack, hoisted out of the layer scan by the
    # compiler, carries no metadata at all
    assert decode.scope_of["convert.67"] == ""
    assert decode.scope_of["while.12"] == ""
    prefill = trace.parse_program_text(_data("prefill_cycle.hlo.txt"))
    assert prefill.module == "jit_hvd_serve_prefill"
    assert prefill.scope_of["fusion.157"] == "hvd_attention"
    with pytest.raises(ValueError, match="HloModule"):
        trace.parse_program_text("ENTRY %main { }")


def test_a_repeated_instruction_name_reads_its_own_programs_scope(cycle):
    """``fusion.157`` ran in both programs: 9 243 ns under ``hvd_attention``
    in the prefill run, 23 686 ns under ``hvd_mlp`` in the decode run; so did
    ``add_rsqrt_fusion.6`` (20 and 21 ns, ``hvd_mlp`` in both)."""
    prefill = cycle.scope_op_s["jit_hvd_serve_prefill"]
    decode = cycle.scope_op_s["jit_hvd_serve_decode"]
    # prefill: fusion.157 9243 + fusion.193 15760
    assert _ns(prefill["hvd_attention"]) == {"fusion": 25_003}
    assert _ns(prefill["hvd_mlp"]) == {"add_rsqrt_fusion": 20,
                                       "fusion": 29_151}
    assert _ns(prefill["hvd_kv_write"]) == {"fusion": 22_467}
    assert _ns(decode["hvd_mlp"]) == {"add_rsqrt_fusion": 21,
                                      "fusion": 23_686}
    assert _ns(decode["hvd_kv_write"]) == {"compare_select_fusion": 12,
                                           "fusion": 1_937}
    assert _ns(decode["hvd_attention/hvd_paged_decode"]) == {
        "hvd_paged_decode": 139_641}
    assert "hvd_attention" not in decode


def test_time_under_no_scope_is_reported_as_such(cycle):
    # decode: the weight cast 913376, fusion.156 3983 + fusion.91 273723, and
    # the while's own time, 4773287 less its six children's 169280
    assert _ns(cycle.scope_op_s["jit_hvd_serve_decode"][trace.UNSCOPED]) == {
        "convert": 913_376, "fusion": 277_706, "while": 4_604_007}
    # prefill: while.14's 4773246 less its seven children's 84879
    assert _ns(cycle.scope_op_s["jit_hvd_serve_prefill"][trace.UNSCOPED]) \
        == {"convert": 912_742, "constant_dynamic-slice_fusion": 3_580,
            "fusion": 4_658, "multiply_reduce_fusion": 273_529,
            "while": 4_688_367}
    split = cycle.scope_seconds(
        "hvd_serve_decode", ("hvd_attention", "hvd_mlp", "hvd_kv_write"))
    # the kernel's own scope lies inside hvd_attention and counts for it
    assert _ns(split) == {"hvd_attention": 139_641, "hvd_mlp": 23_707,
                          "hvd_kv_write": 1_949, "other": 5_795_089}
    # everything the program ran is in one part or another
    own = sum(v for name, v in cycle.op_self_s.items())
    both = sum(sum(ops.values()) for by_scope in cycle.scope_op_s.values()
               for ops in by_scope.values())
    assert both == pytest.approx(own)
    assert cycle.program_runs("hvd_serve_decode") == 1
    # a program whose text did not come is not told by scope at all
    rows = json.loads(_data("serve_cycle_trace.json"))["rows"]
    one = trace.reduce(rows, {"d": _data("decode_cycle.hlo.txt")})
    assert set(one.scope_op_s) == {"jit_hvd_serve_decode"}
    assert one.scope_seconds("hvd_serve_prefill", ("hvd_mlp",)) is None
    assert trace.reduce(rows).scope_op_s == {}


def test_the_bucket_programs_of_one_name_are_told_by_what_they_hold():
    """An engine compiles one prefill program per bucket, all of them
    ``jit_hvd_serve_prefill``: a run belongs to the text that holds most of
    its instruction names."""
    small = trace.ProgramText("jit_p", {"fusion.1": "hvd_mlp", "copy.2": ""})
    large = trace.ProgramText("jit_p", {"fusion.1": "hvd_attention",
                                        "fusion.3": "hvd_mlp", "copy.2": ""})
    pick = trace._text_of_run
    assert pick([small, large], frozenset({"fusion.1", "copy.2"})) is small
    assert pick([small, large], frozenset({"fusion.1", "fusion.3"})) is large
    assert pick([small], frozenset({"nothing.9"})) is small
    assert pick([], frozenset({"fusion.1"})) is None


def test_nested_program_spans_are_kept(cycle):
    names = [s.name for s in cycle.spans]
    assert names == [
        "bench.schedule", "hvd.serve.cycle", "hvd.serve.retire",
        "hvd.serve.admit", "hvd.serve.prefill", "bench.prefill",
        "hvd.engine.prefill.dispatch", "hvd.serve.retire",
        "hvd.serve.decode", "bench.decode", "hvd.engine.decode.dispatch",
        "hvd.engine.decode.wait", "hvd.serve.retire"]
    assert [s.parent for s in cycle.spans] == [
        -1, 0, 1, 1, 1, 4, 5, 1, 1, 8, 9, 9, 1]
    # from the window's start
    assert round(cycle.spans[1].start_s / NS) == 2_180
    assert _ns({"wait": cycle.span_seconds("hvd.engine.decode.wait")[0]}) \
        == {"wait": 15_917_320}
    # the cycle's 19324429 less retire 3910 + 9950 + 9049, admit 1120,
    # prefill 1810620 and decode 17469899
    assert [round(s / NS) for s in
            cycle.span_self_seconds("hvd.serve.cycle")] == [19_881]
    assert cycle.span_seconds("hvd.serve.nonesuch") == []


def test_idle_gaps_are_named_by_the_innermost_span(cycle):
    gaps = _ns(dict(cycle.idle_gaps))
    # until the first operation kept (convert.80 at 843761 ns) the host was
    # in bench.schedule 2180, serve.cycle 4120, retire 3910, admit 1120,
    # serve.prefill 4490, bench.prefill 9210 and engine.prefill.dispatch
    # 818731; the dispatch span outlasts that operation by 28767 more
    assert gaps["hvd.engine.prefill.dispatch"] == 818_731 + 28_767
    assert gaps["hvd.serve.admit"] == 1_120
    assert gaps["hvd.engine.decode.dispatch"] == 1_279_050     # all of it
    # after bench.schedule's end the window has 21740 ns left
    assert gaps["_no_span_"] == 21_740
    assert sum(gaps.values()) == round(
        (cycle.window_s - cycle.busy_s) / NS) == 19_350_800 - 11_919_903
