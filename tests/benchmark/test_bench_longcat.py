"""The ``longcat_flash`` family under the tier-1 suite: a CPU rehearsal of
its toy cell through the one command's code; its weights, drawn a leaf or a
layer at a time; the readers of the per-layer metrics it brings, on numbers
worked by hand; the configuration file against the published sizes."""

import importlib
import json
import os
import time

import jax
import numpy as np
import pytest

import bench_toy_longcat
from benchmarks.lib import cell as cells, chip, report, trees
from test_bench_spec import _made_up_run

CELL = "longcat_flash_omni_serve_c64"


def _record(seconds=0.3, seed=7):
    c = bench_toy_longcat.cell()
    kind = importlib.import_module("benchmarks.kinds." + c.traffic["kind"])
    devices = chip.take_chips(1, require_tpu=False)
    return kind.run(c, seed, seconds, 0, devices, time.perf_counter(),
                    chip.CompileLog())


def test_toy_cell_runs_end_to_end():
    from benchmarks import run
    c = bench_toy_longcat.cell()
    line = run.run_cell(c, 2 ** 31 + 11, 0.3, 0, require_tpu=False,
                        t_start=time.perf_counter())
    json.dumps(line)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    (value, limit), = line["compared"].values()
    assert 0 <= value <= limit


def test_the_familys_counters_arrive_as_the_windows_difference():
    rec = _record()
    c = rec.counters
    assert len(c["decode_keys"]) == len(rec.unit_s) > 0
    total = (c["moe_assignments_held"] + c["moe_assignments_zero"]
             + c["moe_assignments_absent"])
    rows = c["prefill_tokens"] + sum(len(k) for k in c["decode_keys"])
    model = rec.program["model"]
    # every row of the window routed moe_topk times in each layer
    assert total == rows * model["moe_topk"] * model["num_layers"]
    held = [c[f"moe_expert_rows.{j}"] for j in range(4)]
    assert sum(held) == c["moe_assignments_held"] > 0
    assert 0 < c["moe_decode_experts_active"] <= c["moe_experts_active"]
    assert c["moe_decode_experts_active"] <= 4 * model["num_layers"] * len(
        c["decode_keys"])
    # the held experts' products are in the required operations
    from benchmarks.roofline import longcat_flash as cost
    assert c["required_flops"] > c["moe_assignments_held"] * cost.expert_flops(
        model) > 0
    # the counter readers read the record as it is
    assert report.read_metric("moe_held_assignments_per_token", rec) \
        == pytest.approx(3 * c["moe_assignments_held"] / total)
    assert 0 < report.read_metric("moe_zero_expert_share", rec) < 1
    assert report.read_metric("moe_expert_load_max_over_mean", rec) >= 1
    # untraced: nothing for the trace's readers, and no error
    for name in ("decode_hbm_roofline", "moe_scope_ms_per_decode_step.experts",
                 "mla_ms_per_decode_step.proj"):
        assert report.read_metric(name, rec) is None


def test_weights_a_layer_at_a_time_are_the_stacked_leaves_slices():
    from benchmarks.families import longcat_flash as fam
    config = bench_toy_longcat.cell().config
    big = 2 ** 31 + 12345
    w = fam.weights(config, trees.key_from_seed(big))
    again = fam.weights(config, trees.key_from_seed(big))
    other = fam.weights(config, trees.key_from_seed(big + 1))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(again)))
    assert not np.array_equal(w["embed"], other["embed"])
    for l in range(config["num_layers"]):
        lw = fam.layer_weights(config, trees.key_from_seed(big), l)
        flat = jax.tree_util.tree_flatten_with_path(lw)[0]
        want = jax.tree.leaves(jax.tree.map(lambda a: a[l], w["layers"]))
        for (path, got), ref in zip(flat, want):
            assert got.dtype == np.float32
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref, np.float32),
                err_msg=jax.tree_util.keystr(path))
    top = fam.top_weights(config, trees.key_from_seed(big))
    for name in ("embed", "final_norm", "head"):
        np.testing.assert_array_equal(np.asarray(top[name]),
                                      np.asarray(w[name], np.float32))
    # bfloat16 on the device, the router and the scales float32; the
    # program's own tree has the same leaves and shapes
    from horovod_tpu.models import longcat_flash as lc
    mine = {jax.tree_util.keystr(p): (a.shape, str(a.dtype)) for p, a in
            jax.tree_util.tree_flatten_with_path(w)[0]}
    theirs = jax.eval_shape(lambda: lc.init_params(
        fam.program_config(config), jax.random.PRNGKey(0)))
    assert mine == {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
                    for p, a in
                    jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert mine["['layers']['moe']['w_gate']"] == ((2, 4, 64, 32), "bfloat16")
    assert mine["['layers']['moe']['router']"] == ((2, 64, 12), "float32")
    # the draws' gains (the configuration file's ``assumed.weights``): the
    # router's, and queries and keys at unit scale whatever a_q and a_kv
    def std(leaf):
        return float(np.std(np.asarray(leaf, np.float32)))
    assert std(w["layers"]["moe"]["router"]) == pytest.approx(
        fam.ROUTER_GAIN / 64 ** 0.5, rel=0.1)
    blk = w["layers"]["mla"][1]
    assert std(blk["wq_b"]) == pytest.approx(
        24 ** -0.5 * (24 / 64) ** 0.5, rel=0.05)
    assert std(blk["wkv_b"]) == pytest.approx(
        16 ** -0.5 * (16 / 64) ** 0.5, rel=0.05)
    assert std(blk["wq_a"]) == pytest.approx(64 ** -0.5, rel=0.05)


def test_the_configuration_keeps_the_published_widths():
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "longcat_flash_omni.json"))
    # meituan-longcat/LongCat-Flash-Omni config.json, but for the three cuts
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert config["reduced"] == list(cut)
    assert config["published"] == {k: published[k] for k in cut}
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    # the guide's floors: four layers, eight experts, an eighth of the rows
    assert config["num_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    from benchmarks.roofline import longcat_flash as cost
    p = cost.parameters(config)
    assert p["layer_outside_experts"] == pytest.approx(638.9e6, rel=1e-3)
    assert p["expert"] == 3 * 6144 * 2048
    held = (config["num_layers"] * (p["layer_outside_experts"]
                                    + 16 * p["expert"]) + 2 * p["head"])
    assert held == pytest.approx(5.173e9, rel=1e-3)     # 10.35 GB in bf16
    cell = cells.load_cell(CELL)
    engine = cell.traffic["engine"]
    assert (cell.traffic["clients"], engine["slots"], engine["max_seq"],
            engine["page"], engine["prefill_chunk"]) == (64, 64, 1024, 128, 256)
    assert cell.chips == 1


def test_readers_on_numbers_worked_by_hand():
    rec = _made_up_run(CELL)
    metrics_dir = os.path.join(cells.BENCH_DIR, "metrics")
    names = ("moe_scope_ms_per_decode_step.router", "moe_scope_ms_per_decode_step.experts",
             "moe_scope_ms_per_decode_step.combine", "mla_ms_per_decode_step.proj",
             "moe_held_assignments_per_token", "moe_zero_expert_share",
             "moe_expert_load_max_over_mean", "decode_hbm_roofline")
    assert set(names) <= {m["name"] for m in rec.cell.per_layer}
    for name in names:      # nothing to read until the program has it
        assert report.read_metric(name, rec) is None, name
    for name in names:
        report.load_reader(name, metrics_dir)[0].example(rec)
    # the made-up window holds 4 decode runs
    assert report.read_metric("moe_scope_ms_per_decode_step.router", rec) \
        == pytest.approx(0.5)
    assert report.read_metric("moe_scope_ms_per_decode_step.experts", rec) \
        == pytest.approx(6.0)
    assert report.read_metric("moe_scope_ms_per_decode_step.combine", rec) \
        == pytest.approx(0.25)
    assert report.read_metric("mla_ms_per_decode_step.proj", rec) \
        == pytest.approx(2.0)
    # what the expert block and the projections take is in ``.other`` of the
    # decode step's split, which names neither
    assert report.read_metric("scope_ms_per_decode_step.other", rec) \
        == pytest.approx(1e3 * (0.044 + 0.002 + 0.024 + 0.001 + 0.008) / 4)
    assert report.read_metric("moe_held_assignments_per_token", rec) \
        == pytest.approx(12 * 250 / 12000)
    assert report.read_metric("moe_zero_expert_share", rec) \
        == pytest.approx(4000 / 12000)
    assert report.read_metric("moe_expert_load_max_over_mean", rec) \
        == pytest.approx(60 / 30)
    # a step of 2 slots holding 300 and 500 keys: 4 layers of 638.9 M
    # parameters outside the experts (the router's 4.7 M at 4 bytes), the
    # head's 100.7 M, 2 embedding rows, 40 experts of 37.75 M, 800 keys of
    # 8 x 576 numbers; in bfloat16, at 819 GB/s, over 25 ms
    bytes_ = (4 * ((638_844_928 - 4_718_592) * 2 + 4_718_592 * 4)
              + 100_663_296 * 2 + 64 * 6144 * 2 + 40 * 37_748_736 * 2
              + 800 * 8 * 576 * 2)
    assert report.read_metric("decode_hbm_roofline", rec) == pytest.approx(
        100 * bytes_ / 819e9 / 0.025)
    assert 0 < report.read_metric("decode_hbm_roofline", rec) <= 100
