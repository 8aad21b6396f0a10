"""The ``solar_open2`` family under the tier-1 suite: a CPU rehearsal of its
toy cell through the one command's code; its weights, drawn a leaf or a
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

import bench_toy_solar
from benchmarks.lib import cell as cells, chip, report, trees
from test_bench_spec import _made_up_run, configuration_rule

CELL = "solar_open2_250b_serve_c128"
NEW_READERS = (
    "kda_ms_per_decode_step.proj", "kda_ms_per_decode_step.conv",
    "kda_ms_per_decode_step.scan", "kda_ms_per_decode_step.gate",
    "kda_ms_per_prefill_chunk.proj", "kda_ms_per_prefill_chunk.conv",
    "kda_ms_per_prefill_chunk.scan", "kda_ms_per_prefill_chunk.gate",
    "kda_decode_hbm_roofline", "kda_chunks_carried_share", "kda_state_gb")


@pytest.fixture(scope="module")
def record():
    c = bench_toy_solar.cell()
    kind = importlib.import_module("benchmarks.kinds." + c.traffic["kind"])
    devices = chip.take_chips(1, require_tpu=False)
    return kind.run(c, 7, 0.3, 0, devices, time.perf_counter(),
                    chip.CompileLog())


def test_toy_cell_runs_end_to_end():
    from benchmarks import run
    c = bench_toy_solar.cell()
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
        * model["num_hidden_layers"]
    assert c["moe_assignments_zero"] == 0
    held = [c[f"moe_expert_rows.{j}"] for j in range(4)]
    assert sum(held) == c["moe_assignments_held"] > 0
    assert 0 < c["moe_decode_experts_active"] <= c["moe_experts_active"]
    # every decode row advanced one slot's state; chunks opened or carried
    assert c["ssm_decode_rows"] == sum(len(k) for k in c["decode_keys"])
    assert c["ssm_resets"] > 0 and c["ssm_chunks_carried"] > 0
    from benchmarks.roofline import solar_open2 as cost
    assert c["required_flops"] > c["moe_assignments_held"] * cost.expert_flops(
        model) > 0
    # 4 slots x 3 KDA layers x (4 x 16 x 16 state + 3 x 192 tail) float32
    assert rec.program["ssm_state_bytes"] == 4 * 3 * (1024 + 3 * 192) * 4


def test_the_counter_readers_read_the_record_as_it_is(record):
    rec, c = record, record.counters
    total = (c["moe_assignments_held"] + c["moe_assignments_absent"])
    assert report.read_metric("moe_held_assignments_per_token", rec) \
        == pytest.approx(3 * c["moe_assignments_held"] / total)
    assert report.read_metric("moe_expert_load_max_over_mean", rec) >= 1
    assert report.read_metric("kda_chunks_carried_share", rec) \
        == pytest.approx(c["ssm_chunks_carried"]
                         / (c["ssm_chunks_carried"] + c["ssm_resets"]))
    assert report.read_metric("kda_state_gb", rec) == pytest.approx(76800e-9)


@pytest.mark.parametrize("name", [n for n in NEW_READERS if n not in (
    "kda_chunks_carried_share", "kda_state_gb")] + [
        "moe_scope_ms_per_decode_step.experts", "ssm_decode_hbm_roofline"])
def test_a_trace_reader_returns_nothing_on_an_untraced_run(record, name):
    assert report.read_metric(name, record) is None


def test_weights_a_layer_at_a_time_are_the_stacked_leaves_slices():
    from benchmarks.families import solar_open2 as fam
    from benchmarks.reference import solar_open2 as ref
    config = bench_toy_solar.cell().config
    big = 2 ** 31 + 12345
    w = fam.weights(config, trees.key_from_seed(big))
    again = fam.weights(config, trees.key_from_seed(big))
    other = fam.weights(config, trees.key_from_seed(big + 1))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(again)))
    assert not np.array_equal(w["embed"], other["embed"])
    seen = {"kda": 0, "attention": 0}
    kinds = ref.layer_types_of(config)
    assert kinds == ("attention", "kda", "kda", "kda")
    for l, kind in enumerate(kinds):
        got_kind, mixer, expert = fam.layer_weights(
            config, trees.key_from_seed(big), l)
        assert got_kind == kind
        for got, stack, at in ((mixer, w["layers"][kind], seen[kind]),
                               (expert, w["layers"]["moe"], l)):
            flat = jax.tree_util.tree_flatten_with_path(got)[0]
            want = jax.tree.leaves(jax.tree.map(lambda a: a[at], stack))
            for (path, leaf), ref_leaf in zip(flat, want):
                assert leaf.dtype == np.float32
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(ref_leaf, np.float32),
                    err_msg=f"{l} {jax.tree_util.keystr(path)}")
        seen[kind] += 1
    top = fam.top_weights(config, trees.key_from_seed(big))
    assert set(top) == {"embed", "head", "final_norm"}  # the head is untied
    assert not np.array_equal(top["embed"], top["head"])
    for name in top:
        np.testing.assert_array_equal(np.asarray(top[name]),
                                      np.asarray(w[name], np.float32))
    # bfloat16 products on the device, float32 the router and the small
    # leaves; the program's own tree has the same leaves and shapes
    from horovod_tpu.models import solar_open2 as so
    mine = {jax.tree_util.keystr(p): (a.shape, str(a.dtype)) for p, a in
            jax.tree_util.tree_flatten_with_path(w)[0]}
    theirs = jax.eval_shape(lambda: so.init_params(
        fam.program_config(config), jax.random.PRNGKey(0)))
    assert mine == {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
                    for p, a in
                    jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert mine["['layers']['moe']['w_gate']"] == ((4, 4, 64, 32), "bfloat16")
    assert mine["['layers']['moe']['router']"] == ((4, 64, 8), "float32")
    assert mine["['layers']['kda']['w_qkv']"] == ((3, 64, 192), "bfloat16")
    assert mine["['layers']['kda']['w_f2']"] == ((3, 16, 64), "bfloat16")
    assert mine["['layers']['kda']['A_log']"] == ((3, 4), "float32")
    assert mine["['layers']['kda']['dt_bias']"] == ((3, 64), "float32")
    assert mine["['layers']['attention']['wz']"] == ((1, 64, 64), "bfloat16")

    # the draws (the configuration file's ``assumed``)
    def std(leaf):
        return float(np.std(np.asarray(leaf, np.float32)))
    by_name = {jax.tree_util.keystr(p): a for p, a in
               jax.tree_util.tree_flatten_with_path(w)[0]}
    for name, fan_in in (("['layers']['moe']['router']", 64),
                         ("['layers']['moe']['w_down']", 32),
                         ("['layers']['moe']['shared']['w_down']", 32),
                         ("['layers']['kda']['w_g2']", 16),
                         ("['layers']['kda']['w_f2']", 16),
                         ("['layers']['kda']['w_b']", 64),
                         ("['layers']['kda']['w_qkv']", 64),
                         ("['layers']['attention']['wz']", 64),
                         ("['layers']['attention']['wq']", 64)):
        gain = next((g for end, g in fam.GAINS.items()
                     if name.endswith(end)), 1.0)
        assert std(by_name[name]) == pytest.approx(
            gain / fan_in ** 0.5, rel=0.12), name
    assert all(any(n.endswith(end) for n in by_name) for end in fam.GAINS)
    assert np.all(np.asarray(w["layers"]["moe"]["router_bias"]) == 0.0)
    assert std(w["embed"]) == pytest.approx(fam.EMBED_DEVIATION, rel=0.05)
    assert std(w["head"]) == pytest.approx(64 ** -0.5, rel=0.05)
    m = w["layers"]["kda"]
    assert std(m["w_o"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(m["conv_w"]) == pytest.approx(0.5, rel=0.1)
    a = np.exp(np.asarray(m["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(m["dt_bias"])))       # softplus
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01


def test_the_configuration_keeps_the_published_widths():
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "solar_open2_250b.json"))
    # upstage/Solar-Open2-250B config.json, but for the four cuts
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_routed_experts": 320, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8}
    cut = {"num_hidden_layers": 4, "gqa_layers": [0],
           "n_routed_experts": 40, "vocab_size": 24576}
    assert config["reduced"] == list(cut)
    assert config["published"] == {k: published[k] for k in cut}
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    for key in ("deployment", "assumed", "served_dtype"):
        assert config[key], key
    assert "8 chips sharing each layer" in config["deployment"]
    assert "v5e-64 as 8 pipeline stages" in config["deployment"]
    assert "float32 delta-rule state" in config["served_dtype"]
    assert "bfloat16 weights and K/V pages" in config["served_dtype"]
    for key in ("layer", "kda", "kda_gate_rank", "kda_qkv", "kda_init",
                "attention", "router", "experts", "state_dtype", "weights"):
        assert key in config["assumed"], key
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(c for c in spec["configs"] if c["name"] == "solar_open2_250b")
    configuration_rule(entry, config)
    # the guide's floors: a whole period in its published ratio and four
    # layers, eight experts, an eighth of the rows
    assert config["num_hidden_layers"] >= 4 and config["gqa_layers"] == [0]
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    from benchmarks.roofline import solar_open2 as cost
    p = cost.parameters(config)
    # q, k, v and o; two low-rank gates; beta: 137.7 M
    assert p["kda"] == (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192)
                        + 4096 * 64)
    assert p["attention"] == 3 * 4096 * 8192 + 2 * 4096 * 1024  # 109.1 M
    assert p["expert"] == p["shared"] == 3 * 4096 * 1280        # 15.73 M
    assert p["router"] == 4096 * 320
    held = (3 * (p["kda"] + p["kda_small"]) + p["attention"]
            + 4 * (p["shared"] + p["router"] + p["router_small"]
                   + 40 * p["expert"]) + 2 * p["head"] + 9 * 4096)
    assert held == pytest.approx(3.308e9, rel=1e-3)     # 6.62 GB in bf16
    # the whole model with these shapes is the published 250B
    whole = (36 * (p["kda"] + p["kda_small"]) + 12 * p["attention"]
             + 48 * (p["shared"] + p["router"] + 320 * p["expert"])
             + 2 * 196608 * 4096)
    assert whole == pytest.approx(250.29e9, rel=1e-3)
    assert 128 * 3 * cost.slot_state_numbers(config) * 4 == pytest.approx(
        1.724e9, rel=1e-3)
    cell = cells.load_cell(CELL)
    engine, t = cell.traffic["engine"], cell.traffic
    assert (t["clients"], engine["slots"], engine["max_seq"], engine["page"],
            engine["prefill_chunk"], engine["prefix_cache"]) == (
                128, 128, 2048, 128, 256, False)
    assert (t["prompt_len"], t["output_len"], t["strata"]) == (
        {"dist": "loguniform", "lo": 128, "hi": 1024},
        {"dist": "uniform", "lo": 192, "hi": 576}, 8)
    assert (t["trace_seconds"], t["check_requests"], t["check_pad_to"]) \
        == (6, 6, 2048)
    from benchmarks.kinds import serve_closed
    assert serve_closed.quantile_lengths(t["prompt_len"], 8) == [
        146, 189, 245, 318, 412, 535, 693, 899]
    assert serve_closed.quantile_lengths(t["output_len"], 8) == list(
        range(216, 553, 48))
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
            "scope_ms_per_decode_step.other",
            "moe_scope_ms_per_decode_step.router",
            "moe_scope_ms_per_decode_step.experts",
            "moe_scope_ms_per_decode_step.combine",
            "moe_expert_load_max_over_mean",
            "moe_held_assignments_per_token", "setup_compile_s",
            "compiles_in_window"} <= mine
    assert not {m for m in mine if m.startswith(("ssm_", "mla_",
                                                 "paged_decode_"))}
    assert not mine & {"decode_hbm_roofline", "moe_zero_expert_share",
                       "prefill_ms_per_prompt_token"}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    # the new readers are this cell's alone; the benchmark has seven cells,
    # one of them on four chips
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == "serve_out_tokens_per_s"
    assert len(spec["workloads"]) == 7
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "pythia410m_train_dp4"]
    assert spec["workloads"][-1]["name"] == CELL
    assert spec["configs"][-1]["name"] == "solar_open2_250b"


def test_readers_on_numbers_worked_by_hand():
    rec = _made_up_run(CELL)
    metrics_dir = os.path.join(cells.BENCH_DIR, "metrics")
    for name in NEW_READERS:    # nothing to read until the program has it
        assert report.read_metric(name, rec) is None, name
    for name in NEW_READERS:
        report.load_reader(name, metrics_dir)[0].example(rec)
    # the made-up window holds 4 decode runs and 2 prefill runs
    for part, ms in (("proj", 2.0), ("conv", 0.25), ("scan", 6.0),
                     ("gate", 0.1)):
        assert report.read_metric(f"kda_ms_per_decode_step.{part}", rec) \
            == pytest.approx(ms), part
    for part, ms in (("proj", 3.0), ("conv", 0.3), ("scan", 2.5),
                     ("gate", 0.1)):
        assert report.read_metric(f"kda_ms_per_prefill_chunk.{part}", rec) \
            == pytest.approx(ms), part
    assert report.read_metric("kda_chunks_carried_share", rec) \
        == pytest.approx(50 / 110)
    assert report.read_metric("kda_state_gb", rec) == pytest.approx(1.724)
    # a step of 2 live slots of 128 holding 300 and 500 keys: 3 KDA mixers
    # of 137.6 M (their 106.7 k small leaves in float32), one attention
    # mixer of 109.1 M, four shared experts of 15.73 M and routers of 1.31 M
    # (and their 320 biases, float32), the head's 100.7 M, 128 embedding
    # rows, 150 experts of 15.73 M, 800 keys of 2 x 8 x 128 numbers, in
    # bfloat16; 2 slots' state of 3 x (1 048 576 + 73 728) float32 numbers
    # read and written; at 819 GB/s, over 25 ms
    bytes_ = (3 * (137_625_600 * 2 + 106_688 * 4) + 109_051_904 * 2
              + 4 * (15_728_640 * 2 + (1_310_720 + 320) * 4)
              + 100_663_296 * 2 + 128 * 4096 * 2 + 150 * 15_728_640 * 2
              + 800 * 2 * 8 * 128 * 2 + 2 * 3 * 2 * 1_122_304 * 4)
    assert report.read_metric("kda_decode_hbm_roofline", rec) \
        == pytest.approx(100 * bytes_ / 819e9 / 0.025)
    assert 0 < report.read_metric("kda_decode_hbm_roofline", rec) <= 100
    # the delta-rule scopes are in ``.other`` of the decode step's split
    assert report.read_metric("scope_ms_per_decode_step.other", rec) \
        == pytest.approx(1e3 * (0.044 + 0.008 + 0.001 + 0.024 + 0.0004) / 4)
    # another family's model gives the roofline nothing to read
    rec.program["model"] = {"moe_topk": 12}
    assert report.read_metric("kda_decode_hbm_roofline", rec) is None


def test_the_rooflines_counts_against_a_hand_count_at_the_toy_size():
    from benchmarks.roofline import solar_open2 as cost
    config = bench_toy_solar.cell().config
    p = cost.parameters(config)
    # hidden 64; KDA: 4 heads of 16 (64 channels a stream), gates of rank
    # 16, conv of width 4 over 3 x 64 channels; attention 4 heads of 16 over
    # 2 KV heads, gated; experts of 32, one shared, router over the
    # published 8
    assert p == {"kda": 4 * 64 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4,
                 "kda_small": 3 * 64 * 4 + 64 + 4 + 16,
                 "attention": 3 * 64 * 64 + 2 * 64 * 32, "router": 64 * 8,
                 "router_small": 8, "expert": 3 * 64 * 32,
                 "shared": 3 * 64 * 32, "head": 64 * 256}
    assert cost.layer_counts(config) == {"kda": 3, "attention": 1, "all": 4}
    assert cost.slot_state_numbers(config) == 4 * 16 * 16 + 3 * 192
    assert cost.expert_flops(config) == 2 * 3 * 64 * 32
    # 5 new tokens after 7 cached, one logit row: 3 KDA layers (products
    # and 6 x H d d of recurrence a token), 1 attention layer (its
    # products; 5 x 7 + 15 keys seen, 2 x 4 heads x 2 x 16 a key), 4
    # routers and shared experts, the head once
    want = (3 * (2 * 20_736 + 6 * 1024) * 5
            + (2 * 16_384 * 5 + 2 * 4 * 2 * 16 * (35 + 15))
            + 4 * 2 * (512 + 6144) * 5 + 2 * 16_384)
    assert cost.forward_flops(config, 5, 7, logit_rows=1) == want
    assert cost.forward_flops(config, 1, 0) == (
        3 * (2 * 20_736 + 6 * 1024) + 2 * 16_384 + 2 * 4 * 2 * 16
        + 4 * 2 * (512 + 6144) + 2 * 16_384)
    # a decode step of 4 slots, 3 live, 6 (layer, expert) pairs with a row,
    # 50 cached keys
    want = (3 * (20_736 * 2 + 852 * 4) + 16_384 * 2
            + 4 * (6144 * 2 + 520 * 4) + 16_384 * 2 + 4 * 64 * 2
            + 6 * 6144 * 2 + 50 * 2 * 2 * 16 * 2
            + 2 * 3 * 3 * (1024 + 576) * 4)
    assert cost.decode_step_bytes(config, rows=4, experts_with_rows=6,
                                  cached_tokens=50, live_slots=3) == want
