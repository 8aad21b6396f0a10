"""The ``kimi_k2`` family under the tier-1 suite: a CPU rehearsal of its toy
cell through the one command's code; its weights, drawn a leaf or a layer at
a time; its counters and the readers of the per-layer metrics it brings, on
numbers worked by hand; the configuration file against the catalog's
published sizes; the operations and bytes its rooflines read."""

import importlib
import json
import os
import time

import jax
import numpy as np
import pytest

import bench_toy_kimi
from benchmarks.lib import cell as cells, chip, report, trees
from test_bench_spec import _made_up_run

CELL = "kimi_k2_7_code_serve_c32_p12k"
NEW_READERS = ("mla_ms_per_prefill_chunk.proj",
               "mla_ms_per_prefill_chunk.expand",
               "mla_ms_per_prefill_chunk.attention",
               "mla_live_row_share.decode", "mla_live_row_share.prefill",
               "kimi_decode_hbm_roofline", "kimi_prefill_flops_roofline")


def _record(seconds=0.3, seed=7):
    c = bench_toy_kimi.cell()
    kind = importlib.import_module("benchmarks.kinds." + c.traffic["kind"])
    devices = chip.take_chips(1, require_tpu=False)
    return kind.run(c, seed, seconds, 0, devices, time.perf_counter(),
                    chip.CompileLog())


def test_toy_cell_runs_end_to_end():
    from benchmarks import run
    c = bench_toy_kimi.cell()
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
    model = rec.program["model"]
    assert len(c["decode_keys"]) == len(rec.unit_s) > 0
    total = (c["moe_assignments_held"] + c["moe_assignments_zero"]
             + c["moe_assignments_absent"])
    rows = c["prefill_tokens"] + sum(len(k) for k in c["decode_keys"])
    # every row of the window routed moe_topk times in each expert layer
    # (the leading dense layer routes none); no zero-compute expert
    assert total == rows * model["moe_topk"] * (
        model["num_hidden_layers"] - model["first_k_dense_replace"])
    assert c["moe_assignments_zero"] == 0
    held = [c[f"moe_expert_rows.{j}"] for j in range(12)]
    assert sum(held) == c["moe_assignments_held"] > 0
    # the latent rows: live = each decoded slot's keys, read = every slot's
    # whole table; a chunk lives up to its end and reads the whole table
    context = rec.cell.traffic["engine"]["max_seq"]
    slots = rec.cell.traffic["engine"]["slots"]
    assert c["mla_rows_decode_live"] == sum(sum(k) for k in c["decode_keys"])
    assert c["mla_rows_decode_read"] == len(c["decode_keys"]) * slots \
        * context
    assert c["prefill_chunks"] > 0
    assert c["mla_rows_prefill_read"] == c["prefill_chunks"] * context
    assert c["prefill_tokens"] <= c["mla_rows_prefill_live"] \
        <= c["mla_rows_prefill_read"]
    # the held experts' products are in the required operations, prefill's
    # among them
    from benchmarks.roofline import kimi_k2 as cost
    assert c["required_flops"] > c["prefill_required_flops"] \
        > cost.forward_flops(model, 1) > 0
    # the counter readers read the record as it is; the trace's find
    # nothing untraced, and raise nothing
    for part in ("decode", "prefill"):
        share = report.read_metric(f"mla_live_row_share.{part}", rec)
        assert 0 < share <= 1
    assert report.read_metric("moe_held_assignments_per_token", rec) \
        == pytest.approx(4 * c["moe_assignments_held"] / total)
    assert report.read_metric("moe_expert_load_max_over_mean", rec) >= 1
    for name in ("kimi_decode_hbm_roofline", "kimi_prefill_flops_roofline",
                 "mla_ms_per_prefill_chunk.expand",
                 "mla_ms_per_decode_step.proj"):
        assert report.read_metric(name, rec) is None


def test_weights_a_layer_at_a_time_are_the_stacked_leaves_slices():
    from benchmarks.families import kimi_k2 as fam
    config = bench_toy_kimi.cell().config
    big = 2 ** 31 + 12345
    w = fam.weights(config, trees.key_from_seed(big))
    again = fam.weights(config, trees.key_from_seed(big))
    other = fam.weights(config, trees.key_from_seed(big + 1))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(again)))
    assert not np.array_equal(w["embed"], other["embed"])
    seen = {"dense": 0, "moe": 0}
    for l in range(config["num_hidden_layers"]):
        kind, block, ffn = fam.layer_weights(config, trees.key_from_seed(big),
                                             l)
        assert kind == ("dense" if l == 0 else "moe")
        for group, got_tree, at in (("mla", block, l),
                                    (kind, ffn, seen[kind])):
            want = jax.tree.leaves(jax.tree.map(lambda a: a[at],
                                                w["layers"][group]))
            for (path, got), ref in zip(
                    jax.tree_util.tree_flatten_with_path(got_tree)[0], want):
                assert got.dtype == np.float32
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(ref, np.float32),
                    err_msg=group + jax.tree_util.keystr(path))
        seen[kind] += 1
    top = fam.top_weights(config, trees.key_from_seed(big))
    for name in ("embed", "final_norm", "head"):
        np.testing.assert_array_equal(np.asarray(top[name]),
                                      np.asarray(w[name], np.float32))
    # bfloat16 on the device, the router and the scales float32; the
    # program's own tree has the same leaves and shapes
    from horovod_tpu.models import kimi_k2 as kk
    mine = {jax.tree_util.keystr(p): (a.shape, str(a.dtype)) for p, a in
            jax.tree_util.tree_flatten_with_path(w)[0]}
    theirs = jax.eval_shape(lambda: kk.init_params(
        fam.program_config(config), jax.random.PRNGKey(0)))
    assert mine == {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
                    for p, a in
                    jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert mine["['layers']['moe']['w_gate']"] == ((2, 12, 64, 32),
                                                   "bfloat16")
    assert mine["['layers']['moe']['router']"] == ((2, 64, 32), "float32")
    # the draws' gains (the configuration file's ``assumed.weights``)
    def std(leaf):
        return float(np.std(np.asarray(leaf, np.float32)))
    mscale2 = (0.1 * np.log(4) + 1) ** 2        # the toy's yarn factor 4
    assert std(w["layers"]["mla"]["wq_b"]) == pytest.approx(
        24 ** -0.5 / mscale2, rel=0.05)
    assert std(w["layers"]["mla"]["wkv_b"]) == pytest.approx(
        16 ** -0.5, rel=0.05)
    assert std(w["layers"]["moe"]["w_down"]) == pytest.approx(
        fam.ROUTED_DOWN_GAIN * 32 ** -0.5, rel=0.05)
    assert std(w["layers"]["moe"]["shared"]["w_down"]) == pytest.approx(
        32 ** -0.5, rel=0.05)
    assert std(w["embed"]) == pytest.approx(fam.EMBED_DEVIATION, rel=0.05)


def test_the_program_config_is_the_files():
    from benchmarks.families import kimi_k2 as fam
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "kimi_k2_7_code.json"))
    cfg = fam.program_config(config)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_routed_experts,
            cfg.held_experts, cfg.top_k, cfg.vocab_size) == (
                5, 1, 384, 12, 8, 20480)
    assert cfg.runs() == [("dense", 0, 0, 1), ("moe", 1, 0, 4)]
    assert cfg.rope_scaling.ramp(cfg.rope_theta, cfg.qk_rope_dim) == (8, 20)
    assert cfg.softmax_scale == pytest.approx(0.14468, abs=1e-5)
    assert (cfg.d_shared, cfg.routed_scaling) == (2048, 2.827)


def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog row's ``config`` under its own key, nested
    groups whole, but for the three cuts ``reduced`` names."""
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "kimi_k2_7_code.json"))
    # moonshotai/Kimi-K2.7-Code config.json as the catalog reads it
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "kimi_k2",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 384, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 50000, "routed_scaling_factor": 2.827,
        "scoring_func": "sigmoid", "seq_aux": True,
        "tf_legacy_loss": False, "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 163840}
    cut = {"num_hidden_layers": 5, "n_routed_experts": 12,
           "vocab_size": 20480}
    assert config["reduced"] == list(cut)
    assert config["published"] == {k: published[k] for k in cut}
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    # the guide's floors: the dense layer and four expert layers, eight
    # experts, an eighth of the rows
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    from benchmarks.roofline import kimi_k2 as cost
    p = cost.parameters(config)
    assert p["mla"] == pytest.approx(101.12e6, rel=1e-4)
    assert p["dense"] + p["mla"] == pytest.approx(497.5e6, rel=1e-3)
    assert p["mla"] + p["router"] + p["shared"] == pytest.approx(147.9e6,
                                                                 rel=1e-3)
    assert p["expert"] == 3 * 7168 * 2048
    held = cost.outside_experts(config) + 4 * 12 * p["expert"] \
        + 2 * p["head"]
    assert held == pytest.approx(3.497e9, rel=1e-3)     # 6.99 GB in bf16
    whole = {**config, **published}
    whole_p = (cost.outside_experts(whole) + 60 * 384 * p["expert"]
               + 2 * cost.parameters(whole)["head"])
    assert whole_p == pytest.approx(1.026e12, rel=1e-3)
    cell = cells.load_cell(CELL)
    engine = cell.traffic["engine"]
    assert (cell.traffic["clients"], engine["slots"], engine["max_seq"],
            engine["page"], engine["prefill_chunk"],
            engine["prefix_cache"]) == (32, 32, 12800, 128, 256, False)
    assert cell.traffic["check_pad_to"] == engine["max_seq"]
    assert (cell.traffic["prompt_len"]["lo"], cell.traffic["prompt_len"]["hi"],
            cell.traffic["output_len"]["lo"],
            cell.traffic["output_len"]["hi"]) == (2048, 12288, 128, 512)
    assert cell.chips == 1


def test_the_rooflines_count_what_the_chip_must_do():
    from benchmarks.roofline import kimi_k2 as cost
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "kimi_k2_7_code.json"))
    p = cost.parameters(config)
    # one token at position 999: every weight outside the routed experts
    # twice, 1000 keys of 64 heads x (128 + 64 + 128) in 5 layers, the head
    one = cost.forward_flops(config, 1, 999)
    assert one == pytest.approx(
        2 * (5 * p["mla"] + p["dense"] + 4 * (p["router"] + p["shared"]))
        + 5 * 2 * 64 * 320 * 1000 + 2 * p["head"])
    # a chunk is causal: 256 new tokens after 1000 see 1000 + 1..256 keys
    chunk = cost.forward_flops(config, 256, 1000, logit_rows=0)
    assert chunk - 256 * (one - 5 * 2 * 64 * 320 * 1000 - 2 * p["head"]) \
        == pytest.approx(5 * 2 * 64 * 320 * (256 * 1000 + 256 * 257 // 2))
    # a decode step: the weights once, the router at 4 bytes, the held
    # experts with rows, the live latent rows of 576 numbers in 5 blocks
    b = cost.decode_step_bytes(config, rows=32, experts_with_rows=20,
                               cached_tokens=100_000)
    assert b == pytest.approx(
        (5 * p["mla"] + p["dense"] + 4 * p["shared"] + p["head"]) * 2
        + 4 * p["router"] * 4 + 32 * 7168 * 2 + 20 * p["expert"] * 2
        + 5 * 100_000 * 576 * 2)


def test_readers_on_numbers_worked_by_hand():
    rec = _made_up_run(CELL)
    metrics_dir = os.path.join(cells.BENCH_DIR, "metrics")
    mine = {m["name"] for m in rec.cell.per_layer}
    assert set(NEW_READERS) <= mine
    # the cell reports the serve and expert-model metrics, not LongCat's
    # zero-compute share nor its decode roofline
    assert {"mfu.serve", "decode_step_ms_p50", "moe_held_assignments_per_token",
            "mla_ms_per_decode_step.proj", "see_ms_p50",
            "decode_wait_copy_ms_max"} <= mine
    assert not {"moe_zero_expert_share", "decode_hbm_roofline"} & mine
    for name in NEW_READERS:    # nothing to read until the program has it
        assert report.read_metric(name, rec) is None, name
    for name in NEW_READERS:
        report.load_reader(name, metrics_dir)[0].example(rec)
    # the made-up window holds 2 prefill runs of 0.15 s and 4 decode runs of
    # 0.025 s
    assert report.read_metric("mla_ms_per_prefill_chunk.proj", rec) \
        == pytest.approx(3.0)
    assert report.read_metric("mla_ms_per_prefill_chunk.expand", rec) \
        == pytest.approx(8.0)
    assert report.read_metric("mla_ms_per_prefill_chunk.attention", rec) \
        == pytest.approx(12.0)
    assert report.read_metric("mla_live_row_share.decode", rec) \
        == pytest.approx(600_000 / (4 * 32 * 12800))
    assert report.read_metric("mla_live_row_share.prefill", rec) \
        == pytest.approx(9000 / (3 * 12800))
    # 1.2 TFLOP a chunk at 197 TFLOP/s over 150 ms
    assert report.read_metric("kimi_prefill_flops_roofline", rec) \
        == pytest.approx(100 * 1.2e12 / 197e12 / 0.15)
    # a step of 2 slots holding 300 and 500 keys: 5 blocks of 101.1 M, the
    # dense layer's 396.4 M, 4 shared experts of 44.0 M, the routers' 2.75 M
    # at 4 bytes, the head's 146.8 M, 32 embedding rows, 20 experts of
    # 44.0 M, 800 keys of 5 x 576 numbers; at 819 GB/s, over 25 ms
    bytes_ = ((5 * 101_122_048 + 396_361_728 + 4 * 44_040_192
               + 146_800_640) * 2 + 4 * 2_752_512 * 4 + 32 * 7168 * 2
              + 20 * 44_040_192 * 2 + 800 * 5 * 576 * 2)
    assert report.read_metric("kimi_decode_hbm_roofline", rec) \
        == pytest.approx(100 * bytes_ / 819e9 / 0.025)
    for name in ("kimi_decode_hbm_roofline", "kimi_prefill_flops_roofline"):
        assert 0 < report.read_metric(name, rec) <= 100
