"""The reduction from a trace to numbers, on a small recorded trace whose
numbers are worked by hand, and the kernels' cost functions against
hand-worked operations and bytes."""

import json
import os

import pytest

import bench_toy
from benchmarks.lib import readers, trace
from benchmarks.roofline import flash_attention, model_flops, paged_decode

NS = 1e-9


@pytest.fixture(scope="module")
def summary():
    with open(os.path.join(bench_toy.DATA, "small_trace.json")) as f:
        return trace.reduce(json.load(f)["rows"])


def test_interval_algebra():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.total([(0, 3), (5, 6)]) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace.clip([(0, 5), (8, 12)], 4, 10) == [(4, 5), (8, 10)]
    assert trace.overlap([(0, 4)], [(3, 6)]) == 1
    assert trace.op_name("%fusion.12.3 = f32[]") == "fusion"
    assert trace.op_name("hvd_flash_bwd_dkv.7") == "hvd_flash_bwd_dkv"


def test_window_busy_and_idle(summary):
    assert summary.devices == 2
    assert summary.window_s == pytest.approx(10_000 * NS)
    # device 0: [500, 3500) u [3500, 4500) u [4500, 5000) u [5500, 10000)
    #   = 4500 + 4500 = 9000; device 1, shifted by 100: 4500 + 4400 = 8900
    assert summary.busy_s == pytest.approx((9_000 + 8_900) / 2 * NS)


def test_kernel_time_by_name(summary):
    # two forward calls of 1000 + 900 on each device; mean over devices
    assert summary.kernel_s("hvd_flash_fwd") == pytest.approx(1_900 * NS)
    assert summary.kernel_calls("hvd_flash_fwd") == 2
    # dq: 2000 on both (device 1's ends at 7600, inside the window)
    assert summary.kernel_s("hvd_flash_bwd_dq") == pytest.approx(2_000 * NS)
    assert summary.kernel_s("hvd_flash_bwd_dkv") is None     # never ran
    assert summary.kernel_s("hvd_paged_decode") is None


def test_own_time_of_containers(summary):
    # device 0: while 3000 holds 1000 + 800 + 900 = 2700 -> 300 of its own
    assert summary.op_self_s["while"] == pytest.approx(300 * NS)
    # fusion: 800 + 500 + 2000, copy clipped to 500
    assert summary.op_self_s["fusion"] == pytest.approx(3_300 * NS)
    assert summary.op_self_s["copy"] == pytest.approx(500 * NS)
    top = summary.breakdown()["device_ops"]
    assert top[0][0] == "fusion" and len(top) <= 10


def test_exposed_collective_time(summary):
    # the all-reduce of 1000 overlaps no compute on either device
    assert summary.exposed_collective_s == pytest.approx(1_000 * NS)
    rows = [
        {"plane": "/host:CPU", "line": "t", "name": "bench.window",
         "start_ns": 0, "dur_ns": 1_000},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "all-reduce.1",
         "start_ns": 0, "dur_ns": 600},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "fusion.1",
         "start_ns": 200, "dur_ns": 300}]
    # 600 of collective, 300 of it hidden behind the fusion
    assert trace.reduce(rows).exposed_collective_s == pytest.approx(300 * NS)
    # the same all-reduce under the name the compiler gives jax.lax.psum
    rows[1]["name"] = "psum.7"
    assert trace.reduce(rows).exposed_collective_s == pytest.approx(300 * NS)


def test_idle_gaps_named_by_host_span(summary):
    gaps = dict(summary.idle_gaps)
    # device 0 idle: [0, 500) and [5000, 5500). dispatch covers [0, 400),
    # readback [400, 500) and [5000, 5500)
    assert gaps["bench.dispatch"] == pytest.approx(400 * NS)
    assert gaps["bench.readback"] == pytest.approx(600 * NS)
    assert "_no_span_" not in gaps
    assert sum(gaps.values()) == pytest.approx(1_000 * NS)


def test_modules_by_name(summary):
    # 4500 + 4000 on device 0, both inside the window
    assert summary.module_s("train_step") == pytest.approx(8_500 * NS)
    assert summary.module_s("prefill") is None
    # the second run of the step holds no forward kernel; nothing holds a
    # kernel that never ran
    assert summary.module_s(lacking="hvd_flash_fwd") == pytest.approx(
        4_000 * NS)
    assert summary.module_s(lacking="hvd_paged_decode") is None


def test_programs_told_by_what_they_run():
    """Two programs that lost their names: the one that runs the decode
    kernel, and the other."""
    def row(line, name, start, dur):
        return {"plane": "/device:TPU:0", "line": line, "name": name,
                "start_ns": start, "dur_ns": dur}
    rows = [
        {"plane": "/host:CPU", "line": "t", "name": "bench.window",
         "start_ns": 0, "dur_ns": 1_000},
        row("XLA Modules", "jit__unknown(1)", 0, 300),
        row("XLA Ops", "fusion.1", 0, 200),
        row("XLA Ops", "copy.2", 200, 100),
        row("XLA Modules", "jit__unknown(2)", 400, 200),
        row("XLA Ops", "hvd_paged_decode.3", 400, 150),
        row("XLA Ops", "fusion.4", 550, 50),
        row("XLA Modules", "jit__unknown(1)", 700, 500),   # 300 in the window
        row("XLA Ops", "fusion.1", 700, 400)]
    got = trace.reduce(rows)
    assert got.module_s("unknown") == pytest.approx(800 * NS)
    assert got.module_s(lacking="hvd_paged_decode") == pytest.approx(600 * NS)
    assert got.module_total_s == {"jit__unknown": pytest.approx(800 * NS)}


def test_trace_without_device_work_is_refused():
    rows = [{"plane": "/host:CPU", "line": "t", "name": "bench.window",
             "start_ns": 0, "dur_ns": 10}]
    with pytest.raises(ValueError, match="no operation ran"):
        trace.reduce(rows)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce([])


# -- cost functions ------------------------------------------------------------

PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_flash_call_cost_by_hand():
    # the LM cell's call: 4 sequences, 16 heads, 2048 long, head 64, bf16.
    # pairs = 2048 * 2049 / 2 = 2_098_176 per head and sequence
    pairs = 2_098_176
    assert flash_attention._pairs(2048) == pairs
    fwd = flash_attention.forward(4, 16, 2048, 64)
    # two products of 2 * 64 operations per pair
    assert fwd["flops"] == 2 * 2 * 64 * pairs * 4 * 16 == 34_376_515_584
    tensor = 4 * 16 * 2048 * 64 * 2             # 16 MiB
    assert fwd["bytes"] == 4 * tensor + 4 * 16 * 2048 * 4
    dq = flash_attention.backward_dq(4, 16, 2048, 64)
    dkv = flash_attention.backward_dkv(4, 16, 2048, 64)
    assert dq["flops"] == 1.5 * fwd["flops"]
    assert dkv["flops"] == 2.0 * fwd["flops"]
    assert dq["bytes"] == 5 * tensor + 2 * 4 * 16 * 2048 * 4
    assert dkv["bytes"] == 6 * tensor + 2 * 4 * 16 * 2048 * 4
    # compute-bound: 34.4 GFLOP / 197 TFLOP/s = 174.5 us against 82 us of
    # bytes
    assert flash_attention.least_seconds(fwd, PEAK) == pytest.approx(
        34_376_515_584 / 197e12)
    assert 67_633_152 / 819e9 < 34_376_515_584 / 197e12


def test_paged_decode_call_cost_by_hand():
    # three slots in use with 100, 1000 and 129 cached keys, 16 heads of 64
    cost = paged_decode.call([100, 1000, 129], 16, 64)
    keys = 1229
    assert cost["flops"] == 2 * 2 * keys * 16 * 64 == 5_033_984
    assert cost["bytes"] == 2 * keys * 16 * 64 * 2 + 2 * 3 * 16 * 64 * 2
    # memory-bound: 5.03 MB / 819 GB/s = 6.1 us against 0.026 us of compute
    assert flash_attention.least_seconds(cost, PEAK) == pytest.approx(
        cost["bytes"] / 819e9)


def test_model_flops_by_hand():
    lm = bench_toy.load_json(os.path.join(
        os.path.dirname(bench_toy.DATA), "..", "..", "benchmarks", "configs",
        "pythia410m.json"))
    # per layer 4 * 1024 * 1024 + 2 * 1024 * 4096 = 12_582_912 weights in
    # products; 24 layers + the head 1024 * 50304
    weights = 24 * 12_582_912 + 1024 * 50304
    assert weights == 353_501_184
    one = model_flops.lm_forward_flops(lm, 1, context_before=0)
    assert one == 2 * weights + 2 * 2 * 1024 * 1 * 24
    # a decode token with 999 keys before it sees 1000 keys
    tok = model_flops.lm_forward_flops(lm, 1, context_before=999)
    assert tok - one == 2 * 2 * 1024 * 999 * 24
    # a prefill chunk that needs no logits leaves the head out
    chunk = model_flops.lm_forward_flops(lm, 256, 256, logit_rows=0)
    assert chunk == 2 * 24 * 12_582_912 * 256 + 4 * 1024 * 24 * (
        256 * 256 + 256 * 257 // 2)
    per_token = model_flops.lm_train_flops_per_token(lm, 2048)
    assert per_token == pytest.approx(2.4231e9, rel=1e-4)
    cnn = bench_toy.load_json(os.path.join(
        os.path.dirname(bench_toy.DATA), "..", "..", "benchmarks", "configs",
        "resnet50.json"))
    convs = model_flops.resnet_convs(cnn)
    assert len(convs) == 1 + 16 * 3 + 4 + 1     # stem, blocks, shortcuts, fc
    forward = sum(2 * k * k * ci * co * h * w for k, ci, co, h, w in convs)
    assert forward == pytest.approx(8.178e9, rel=1e-3)      # 4.09 GMACs
    stem = 2 * 7 * 7 * 3 * 64 * 112 * 112
    assert model_flops.resnet_train_flops_per_image(cnn) == pytest.approx(
        3 * forward - stem)


def test_allreduce_bytes_from_hlo_text():
    hlo = """
  %all-reduce.1 = f32[1024,256]{1,0:T(8,128)} all-reduce(%p), replica_groups={{0,1,2,3}}
  %ar = (f32[10]{0}, bf16[4,4]{1,0:T(8,128)(2,1)}) all-reduce-start(%a, %b), replica_groups={}
  %done = (f32[10]{0}, bf16[4,4]{1,0}) all-reduce-done(%ar)
  %x = f32[8]{0} add(%y, %z)
"""
    assert readers.allreduce_bytes(hlo) == 1024 * 256 * 4 + 10 * 4 + 16 * 2
    assert readers.allreduce_bytes("") == 0
