"""The ``granite_hybrid`` family under the tier-1 suite: a CPU rehearsal of
its toy cell through the one command's code; its weights, drawn a leaf or a
layer at a time; the readers of the per-layer metrics it brings, on numbers
worked by hand; the roofline's counts against a hand count at the toy size;
the configuration file against the published sizes."""

import importlib
import json
import os
import time

import jax
import numpy as np
import pytest

import bench_toy_granite
from benchmarks.lib import cell as cells, chip, report, trees
from test_bench_spec import _made_up_run, configuration_rule

CELL = "granite_4_0_h_small_serve_c64"
NEW_READERS = (
    "ssm_ms_per_decode_step.proj", "ssm_ms_per_decode_step.conv",
    "ssm_ms_per_decode_step.scan", "ssm_ms_per_decode_step.gate",
    "ssm_ms_per_prefill_chunk.proj", "ssm_ms_per_prefill_chunk.conv",
    "ssm_ms_per_prefill_chunk.scan", "ssm_ms_per_prefill_chunk.gate",
    "ssm_decode_hbm_roofline", "ssm_chunks_carried_share", "ssm_state_gb")


@pytest.fixture(scope="module")
def record():
    c = bench_toy_granite.cell()
    kind = importlib.import_module("benchmarks.kinds." + c.traffic["kind"])
    devices = chip.take_chips(1, require_tpu=False)
    return kind.run(c, 7, 0.3, 0, devices, time.perf_counter(),
                    chip.CompileLog())


def test_toy_cell_runs_end_to_end():
    from benchmarks import run
    c = bench_toy_granite.cell()
    line = run.run_cell(c, 2 ** 31 + 11, 0.3, 0, require_tpu=False,
                        t_start=time.perf_counter())
    json.dumps(line)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    (value, limit), = line["compared"].values()
    assert 0 <= value <= limit


def test_the_familys_counters_arrive_as_the_windows_difference(record):
    rec, c = record, record.counters
    assert len(c["decode_keys"]) == len(rec.unit_s) > 0
    total = (c["moe_assignments_held"] + c["moe_assignments_zero"]
             + c["moe_assignments_absent"])
    rows = c["prefill_tokens"] + sum(len(k) for k in c["decode_keys"])
    model = rec.program["model"]
    # every row of the window routed top-k times in each layer; no
    # zero-compute expert in this model
    assert total == rows * model["num_experts_per_tok"] \
        * len(model["layer_types"])
    assert c["moe_assignments_zero"] == 0
    held = [c[f"moe_expert_rows.{j}"] for j in range(4)]
    assert sum(held) == c["moe_assignments_held"] > 0
    assert 0 < c["moe_decode_experts_active"] <= c["moe_experts_active"]
    # every decode row advanced one slot's state; chunks opened or carried
    assert c["ssm_decode_rows"] == sum(len(k) for k in c["decode_keys"])
    assert c["ssm_resets"] > 0 and c["ssm_chunks_carried"] > 0
    from benchmarks.roofline import granite_hybrid as cost
    assert c["required_flops"] > c["moe_assignments_held"] * cost.expert_flops(
        model) > 0
    # 4 slots x 3 Mamba layers x (128 x 16 state + 3 x 160 tail) float32
    assert rec.program["ssm_state_bytes"] == 4 * 3 * (128 * 16 + 3 * 160) * 4


def test_the_counter_readers_read_the_record_as_it_is(record):
    rec, c = record, record.counters
    total = (c["moe_assignments_held"] + c["moe_assignments_absent"])
    assert report.read_metric("moe_held_assignments_per_token", rec) \
        == pytest.approx(3 * c["moe_assignments_held"] / total)
    assert report.read_metric("moe_expert_load_max_over_mean", rec) >= 1
    assert report.read_metric("ssm_chunks_carried_share", rec) \
        == pytest.approx(c["ssm_chunks_carried"]
                         / (c["ssm_chunks_carried"] + c["ssm_resets"]))
    assert report.read_metric("ssm_state_gb", rec) == pytest.approx(
        121344e-9)


@pytest.mark.parametrize("name", [n for n in NEW_READERS if n not in (
    "ssm_chunks_carried_share", "ssm_state_gb")] + [
        "moe_scope_ms_per_decode_step.experts", "decode_hbm_roofline"])
def test_a_trace_reader_returns_nothing_on_an_untraced_run(record, name):
    assert report.read_metric(name, record) is None


def test_weights_a_layer_at_a_time_are_the_stacked_leaves_slices():
    from benchmarks.families import granite_hybrid as fam
    config = bench_toy_granite.cell().config
    big = 2 ** 31 + 12345
    w = fam.weights(config, trees.key_from_seed(big))
    again = fam.weights(config, trees.key_from_seed(big))
    other = fam.weights(config, trees.key_from_seed(big + 1))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(again)))
    assert not np.array_equal(w["embed"], other["embed"])
    seen = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(config["layer_types"]):
        got_kind, mixer, expert = fam.layer_weights(
            config, trees.key_from_seed(big), l)
        assert got_kind == kind
        for got, stack, at in ((mixer, w["layers"][kind], seen[kind]),
                               (expert, w["layers"]["moe"], l)):
            flat = jax.tree_util.tree_flatten_with_path(got)[0]
            want = jax.tree.leaves(jax.tree.map(lambda a: a[at], stack))
            for (path, leaf), ref in zip(flat, want):
                assert leaf.dtype == np.float32
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(ref, np.float32),
                    err_msg=f"{l} {jax.tree_util.keystr(path)}")
        seen[kind] += 1
    top = fam.top_weights(config, trees.key_from_seed(big))
    assert set(top) == {"embed", "final_norm"}      # the head is tied
    for name in top:
        np.testing.assert_array_equal(np.asarray(top[name]),
                                      np.asarray(w[name], np.float32))
    # bfloat16 products on the device, float32 the router and the small
    # leaves; the program's own tree has the same leaves and shapes
    from horovod_tpu.models import granite_hybrid as gh
    mine = {jax.tree_util.keystr(p): (a.shape, str(a.dtype)) for p, a in
            jax.tree_util.tree_flatten_with_path(w)[0]}
    theirs = jax.eval_shape(lambda: gh.init_params(
        fam.program_config(config), jax.random.PRNGKey(0)))
    assert mine == {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
                    for p, a in
                    jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert mine["['layers']['moe']['w_gate']"] == ((4, 4, 64, 32), "bfloat16")
    assert mine["['layers']['moe']['router']"] == ((4, 64, 8), "float32")
    assert mine["['layers']['mamba']['w_in']"] == (
        (3, 64, 128 + 160 + 16), "bfloat16")
    assert mine["['layers']['mamba']['A_log']"] == ((3, 16), "float32")

    # the draws (the configuration file's ``assumed``)
    def std(leaf):
        return float(np.std(np.asarray(leaf, np.float32)))
    assert std(w["layers"]["moe"]["router"]) == pytest.approx(
        fam.ROUTER_GAIN / 64 ** 0.5, rel=0.1)
    assert std(w["embed"]) == pytest.approx(fam.EMBED_GAIN / 64 ** 0.5,
                                            rel=0.05)
    m = w["layers"]["mamba"]
    assert std(m["w_out"]) == pytest.approx(128 ** -0.5, rel=0.05)
    a = np.exp(np.asarray(m["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(m["dt_bias"])))       # softplus
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    assert np.all(np.asarray(m["D"]) == 1.0)
    assert std(m["conv_b"]) > 0


def test_the_configuration_keeps_the_published_widths():
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "granite_4_0_h_small.json"))
    # ibm-granite/granite-4.0-h-small config.json, but for the four cuts
    layer_types = ["attention" if i % 10 == 5 else "mamba"
                   for i in range(40)]
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "layer_types": layer_types, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    cut = {"num_hidden_layers": 10, "layer_types": layer_types[:10],
           "num_local_experts": 36, "vocab_size": 50176}
    assert config["reduced"] == list(cut)
    assert config["published"] == {k: published[k] for k in cut}
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    for key in ("deployment", "assumed", "served_dtype"):
        assert config[key], key
    assert "2 chips sharing each layer" in config["deployment"]
    assert "4 pipeline stages" in config["deployment"]
    assert "float32 recurrent state" in config["served_dtype"]
    for key in ("expert_width", "input_linear", "ssm_init", "weights",
                "state_dtype"):
        assert key in config["assumed"], key
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(c for c in spec["configs"]
                 if c["name"] == "granite_4_0_h_small")
    configuration_rule(entry, config)
    # the guide's floors: a whole period in its published ratio, eight
    # experts, an eighth of the rows
    assert config["layer_types"].count("attention") == 1
    assert config["num_local_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    from benchmarks.roofline import granite_hybrid as cost
    p = cost.parameters(config)
    assert p["mamba"] == 4096 * 16768 + 8192 * 4096             # 102.2 M
    assert p["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 1024  # 41.9 M
    assert p["expert"] == 3 * 4096 * 768                        # 9.437 M
    assert p["shared"] == 3 * 4096 * 1536 and p["router"] == 4096 * 72
    held = (9 * (p["mamba"] + p["mamba_small"]) + p["attention"]
            + 10 * (p["shared"] + p["router"] + 36 * p["expert"])
            + p["head"] + 20 * 4096 + 4096)
    assert held == pytest.approx(4.757e9, rel=1e-3)     # 9.51 GB in bf16
    assert 64 * 9 * cost.slot_state_numbers(config) * 4 == pytest.approx(
        2.474e9, rel=1e-3)
    cell = cells.load_cell(CELL)
    engine, t = cell.traffic["engine"], cell.traffic
    assert (t["clients"], engine["slots"], engine["max_seq"], engine["page"],
            engine["prefill_chunk"], engine["prefix_cache"]) == (
                64, 64, 2048, 128, 256, False)
    assert (t["prompt_len"], t["output_len"], t["strata"]) == (
        {"dist": "loguniform", "lo": 128, "hi": 1536},
        {"dist": "uniform", "lo": 64, "hi": 384}, 8)
    assert (t["trace_seconds"], t["check_requests"], t["check_pad_to"]) \
        == (6, 6, 2048)
    from benchmarks.kinds import serve_closed
    assert serve_closed.quantile_lengths(t["prompt_len"], 8) == [
        150, 204, 278, 380, 518, 707, 964, 1315]
    assert serve_closed.quantile_lengths(t["output_len"], 8) == list(
        range(84, 365, 40))
    assert cell.chips == 1 and "limits_why" in t
    assert t["limits"]["served_logit_gap"] > 0


def test_the_cell_lists_the_serve_readers_and_not_another_models():
    cell = cells.load_cell(CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= mine
    assert {"decode_step_ms_p50", "batch_occupancy", "mfu.serve",
            "serve_ttft_p50_ms", "decode_device_ms_per_step",
            "prefill_device_ms_per_chunk", "decode_dispatch_ms_p50",
            "decode_wait_ms_p50", "sched_self_ms_per_cycle",
            "prefill_chunks_per_cycle", "decode_ahead_share",
            "scope_ms_per_decode_step.attention",
            "moe_scope_ms_per_decode_step.experts",
            "moe_expert_load_max_over_mean",
            "moe_held_assignments_per_token", "setup_compile_s",
            "compiles_in_window"} <= mine
    assert not mine & {"decode_hbm_roofline", "moe_zero_expert_share",
                       "mla_ms_per_decode_step.proj", "paged_decode_roofline",
                       "paged_decode_ms_per_step",
                       "prefill_ms_per_prompt_token"}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    # the new readers are this cell's alone
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == "serve_out_tokens_per_s"


def test_readers_on_numbers_worked_by_hand():
    rec = _made_up_run(CELL)
    metrics_dir = os.path.join(cells.BENCH_DIR, "metrics")
    for name in NEW_READERS:    # nothing to read until the program has it
        assert report.read_metric(name, rec) is None, name
    for name in NEW_READERS:
        report.load_reader(name, metrics_dir)[0].example(rec)
    # the made-up window holds 4 decode runs and 2 prefill runs
    for part, ms in (("proj", 3.0), ("conv", 0.5), ("scan", 7.0),
                     ("gate", 0.25)):
        assert report.read_metric(f"ssm_ms_per_decode_step.{part}", rec) \
            == pytest.approx(ms), part
    for part, ms in (("proj", 5.0), ("conv", 0.5), ("scan", 3.0),
                     ("gate", 0.25)):
        assert report.read_metric(f"ssm_ms_per_prefill_chunk.{part}", rec) \
            == pytest.approx(ms), part
    assert report.read_metric("ssm_chunks_carried_share", rec) \
        == pytest.approx(70 / 110)
    assert report.read_metric("ssm_state_gb", rec) == pytest.approx(2.47)
    # a step of 2 live slots of 64 holding 300 and 500 keys: 9 Mamba mixers
    # of 102.2 M (their 50.8 k small leaves in float32), one attention mixer
    # of 41.9 M, ten shared experts of 18.9 M and routers of 295 k (float32),
    # the head's 205.5 M, 64 embedding rows, 300 experts of 9.437 M, 800 keys
    # of 2 x 8 x 128 numbers, in bfloat16; 2 slots' state of 9 x (1 048 576
    # + 25 344) float32 numbers read and written; at 819 GB/s, over 25 ms
    bytes_ = (9 * (102_236_160 * 2 + 50_816 * 4) + 41_943_040 * 2
              + 10 * (18_874_368 * 2 + 294_912 * 4) + 205_520_896 * 2
              + 64 * 4096 * 2 + 300 * 9_437_184 * 2 + 800 * 2 * 8 * 128 * 2
              + 2 * 9 * 2 * 1_073_920 * 4)
    assert report.read_metric("ssm_decode_hbm_roofline", rec) \
        == pytest.approx(100 * bytes_ / 819e9 / 0.025)
    assert 0 < report.read_metric("ssm_decode_hbm_roofline", rec) <= 100
    # the state-space scopes are in ``.other`` of the decode step's split
    assert report.read_metric("scope_ms_per_decode_step.other", rec) \
        == pytest.approx(1e3 * (0.044 + 0.012 + 0.002 + 0.028 + 0.001) / 4)
    # another family's model gives the roofline nothing to read
    rec.program["model"] = {"moe_topk": 12}
    assert report.read_metric("ssm_decode_hbm_roofline", rec) is None


def test_the_rooflines_counts_against_a_hand_count_at_the_toy_size():
    from benchmarks.roofline import granite_hybrid as cost
    config = bench_toy_granite.cell().config
    p = cost.parameters(config)
    # hidden 64; Mamba: 16 heads of 8 (d_inner 128), state 16, conv over
    # 128 + 32 = 160 channels; attention 4 heads of 16 over 2 KV heads;
    # experts of 32, shared 48, router over the published 8
    assert p == {"mamba": 64 * (128 + 160 + 16) + 128 * 64,
                 "mamba_small": 4 * 160 + 160 + 3 * 16 + 128,
                 "attention": 2 * 64 * 64 + 2 * 64 * 32, "router": 64 * 8,
                 "expert": 3 * 64 * 32, "shared": 3 * 64 * 48,
                 "head": 64 * 256}
    assert cost.slot_state_numbers(config) == 16 * 8 * 16 + 3 * 160
    assert cost.expert_flops(config) == 2 * 3 * 64 * 32
    # 5 new tokens after 7 cached, one logit row: 3 Mamba layers (products
    # and 2 x 2 x H P N of recurrence a token), 1 attention layer (its
    # products; 5 x 7 + 15 keys seen, 2 x 4 heads x 2 x 16 a key), 4
    # routers and shared experts, the head once
    want = (3 * (2 * 27_648 + 4 * 2048) * 5
            + (2 * 12_288 * 5 + 2 * 4 * 2 * 16 * (35 + 15))
            + 4 * 2 * (512 + 9216) * 5 + 2 * 16_384)
    assert cost.forward_flops(config, 5, 7, logit_rows=1) == want
    assert cost.forward_flops(config, 1, 0) == (
        3 * (2 * 27_648 + 4 * 2048) + 2 * 12_288 + 2 * 4 * 2 * 16
        + 4 * 2 * (512 + 9216) + 2 * 16_384)
    # a decode step of 4 slots, 3 live, 6 (layer, expert) pairs with a row,
    # 50 cached keys
    want = (3 * (27_648 * 2 + 976 * 4) + 12_288 * 2
            + 4 * (9216 * 2 + 512 * 4) + 16_384 * 2 + 4 * 64 * 2
            + 6 * 6144 * 2 + 50 * 2 * 2 * 16 * 2
            + 2 * 3 * 3 * (2048 + 480) * 4)
    assert cost.decode_step_bytes(config, rows=4, experts_with_rows=6,
                                  cached_tokens=50, live_slots=3) == want
