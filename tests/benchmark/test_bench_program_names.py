"""The two readers that tell the engine's compiled programs by name:
``decode_device_ms_per_step`` and ``prefill_device_ms_per_chunk``, on a
summary with named programs, on one whose programs all read ``jit__unknown``
(told by what they hold), and on one with neither."""

import pytest

from benchmarks.lib import cell as cells, programs, report, trace

DECODE, PREFILL = "decode_device_ms_per_step", "prefill_device_ms_per_chunk"
KERNEL = frozenset({"fusion", "hvd_paged_decode"})
PLAIN = frozenset({"fusion", "copy"})


def _run(programs_):
    c = cells.load_cell("pythia410m_serve_closed")
    rec = report.RunRecord(
        cell=c, seed=1, peak={},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    rec.trace = trace.Summary(
        window_s=1.0, busy_s=0.9, devices=1, op_self_s={}, op_total_s={},
        op_calls={}, exposed_collective_s=0.0, idle_gaps=[],
        programs=programs_)
    return rec


NAMED = [("jit_hvd_serve_decode", 0.050, KERNEL),
         ("jit_hvd_serve_prefill", 0.040, PLAIN),
         ("jit_hvd_serve_decode", 0.054, KERNEL),
         ("jit_hvd_serve_prefill", 0.060, PLAIN),
         ("jit_hvd_serve_prefill", 0.020, PLAIN),
         # a stranger in the window belongs to neither
         ("jit_convert_element_type", 0.5, PLAIN)]
UNNAMED = [("jit__unknown", 0.050, KERNEL), ("jit__unknown", 0.040, PLAIN),
           ("jit__unknown", 0.054, KERNEL), ("jit__unknown", 0.060, PLAIN),
           # nor does it by what it holds: it is not one of the engine's
           ("jit_convert_element_type", 6e-7, frozenset())]


@pytest.mark.parametrize("programs_, decode_ms, prefill_ms", [
    pytest.param(NAMED, 52.0, 40.0, id="by_name"),
    pytest.param(UNNAMED, 52.0, 50.0, id="by_what_they_hold"),
    # the kernel replaced: the names still tell the two apart
    pytest.param([(n, s, PLAIN) for n, s, _ in NAMED], 52.0, 40.0,
                 id="by_name_without_the_kernel"),
    # only decode ran in the window
    pytest.param(NAMED[:1], 50.0, None, id="no_prefill_in_the_window"),
    # unnamed and no run holds the kernel: nothing tells them apart
    pytest.param([("jit__unknown", 0.3, PLAIN)], None, None, id="neither"),
    pytest.param([], None, None, id="no_program"),
])
def test_device_ms_of_the_engines_programs(programs_, decode_ms, prefill_ms):
    rec = _run(programs_)
    for metric, want in ((DECODE, decode_ms), (PREFILL, prefill_ms)):
        got = report.read_metric(metric, rec)
        if want is None:
            assert got is None, metric
        else:
            assert got == pytest.approx(want), metric


def test_an_untraced_run_reads_nothing():
    rec = _run(NAMED)
    rec.trace = None
    assert report.read_metric(DECODE, rec) is None
    assert report.read_metric(PREFILL, rec) is None


def test_the_fallback_is_the_older_readers_rule():
    """``prefill_ms_per_prompt_token`` sums what this reader averages."""
    rec = _run(UNNAMED)
    rec.counters = {"prefill_tokens": 500}
    seconds = programs.run_seconds(rec.trace, "hvd_serve_prefill", False)
    assert seconds == [0.040, 0.060]
    # the sum takes the stranger's 0.6 us in; a mean over runs could not
    assert sum(seconds) == pytest.approx(
        rec.trace.module_s(lacking="hvd_paged_decode"), abs=1e-6)
    assert report.read_metric("prefill_ms_per_prompt_token", rec) == \
        pytest.approx(1e3 * sum(seconds) / 500, abs=1e-5)


def test_the_cell_lists_both_and_the_spec_names_their_source():
    c = cells.load_cell("pythia410m_serve_closed")
    mine = {m["name"]: m for m in c.per_layer}
    for name in (DECODE, PREFILL):
        assert mine[name]["source"] == "device_trace"
        assert mine[name]["layer"] == "serve"
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
    for w in ("pythia410m_train_1chip", "resnet50_train_1chip",
              "pythia410m_train_dp4"):
        assert not {DECODE, PREFILL} & {
            m["name"] for m in cells.load_cell(w).per_layer}
