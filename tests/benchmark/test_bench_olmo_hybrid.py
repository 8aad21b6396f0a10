"""The ``olmo_hybrid`` family under the tier-1 suite: a CPU rehearsal of its
toy cell through the one command's code; its weights, drawn in blocks and a
layer at a time; its counters and the readers of the per-layer metrics it
brings, on numbers worked by hand; the configuration file against the
catalog's published sizes; the operations and bytes its rooflines read."""

import importlib
import json
import os
import time

import jax
import numpy as np
import pytest

import bench_toy_olmo
from benchmarks.lib import cell as cells, chip, report, trees
from test_bench_spec import _made_up_run, configuration_rule

CELL = "olmo_hybrid_7b_serve_c64_p2k"
NEW_READERS = (
    "gdn_ms_per_decode_step.proj", "gdn_ms_per_decode_step.conv",
    "gdn_ms_per_decode_step.scan", "gdn_ms_per_decode_step.gate",
    "gdn_ms_per_prefill_chunk.proj", "gdn_ms_per_prefill_chunk.conv",
    "gdn_ms_per_prefill_chunk.scan", "gdn_ms_per_prefill_chunk.gate",
    "gdn_chunks_carried_share", "gdn_state_gb", "gdn_decode_hbm_roofline",
    "gdn_prefill_flops_roofline")
SHARED_SERVE_READERS = {
    "decode_step_ms_p50", "batch_occupancy", "mfu.serve", "serve_ttft_p50_ms",
    "decode_device_ms_per_step", "prefill_device_ms_per_chunk",
    "decode_dispatch_ms_p50", "decode_wait_ms_p50", "sched_self_ms_per_cycle",
    "prefill_chunks_per_cycle", "scope_ms_per_decode_step.attention",
    "scope_ms_per_decode_step.mlp", "scope_ms_per_decode_step.kv_write",
    "scope_ms_per_decode_step.other", "decode_ahead_share",
    "host_turn_ms_p50", "host_turn_ms_max_over_p50", "see_ms_p50",
    "host_gc_ms_per_s", "host_gc_pause_ms_max", "decode_wait_copy_ms_p50",
    "decode_wait_copy_ms_max"}


def _config():
    return cells.load_json(os.path.join(cells.BENCH_DIR, "configs",
                                        "olmo_hybrid_7b.json"))


@pytest.fixture(scope="module")
def record():
    c = bench_toy_olmo.cell()
    kind = importlib.import_module("benchmarks.kinds." + c.traffic["kind"])
    devices = chip.take_chips(1, require_tpu=False)
    return kind.run(c, 7, 0.3, 0, devices, time.perf_counter(),
                    chip.CompileLog())


def test_toy_cell_runs_end_to_end():
    from benchmarks import run
    c = bench_toy_olmo.cell()
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
    # every decode row advanced one slot's state; chunks opened or carried;
    # no routing counters in a dense stack
    assert c["ssm_decode_rows"] == sum(len(k) for k in c["decode_keys"])
    assert c["ssm_resets"] > 0 and c["ssm_chunks_carried"] > 0
    assert c["prefill_chunks"] == c["ssm_resets"] + c["ssm_chunks_carried"]
    assert not [k for k in c if k.startswith("moe_")]
    from benchmarks.roofline import olmo_hybrid as cost
    model = rec.program["model"]
    assert c["required_flops"] > c["prefill_required_flops"] \
        > cost.forward_flops(model, 1) > 0
    # 4 slots x 3 GDN layers x (4 x 16 x 64 state + 3 x 384 tail) float32,
    # as stored and as resident
    assert rec.program["ssm_state_bytes"] == 4 * 3 * (4096 + 3 * 384) * 4
    assert rec.program["ssm_resident_bytes"] == rec.program["ssm_state_bytes"]


def test_the_counter_readers_read_the_record_as_it_is(record):
    rec, c = record, record.counters
    assert report.read_metric("gdn_chunks_carried_share", rec) \
        == pytest.approx(c["ssm_chunks_carried"]
                         / (c["ssm_chunks_carried"] + c["ssm_resets"]))
    assert report.read_metric("gdn_state_gb", rec) == pytest.approx(
        4 * 3 * (4096 + 3 * 384) * 4 / 1e9)


@pytest.mark.parametrize("name", [n for n in NEW_READERS if n not in (
    "gdn_chunks_carried_share", "gdn_state_gb")] + [
        "decode_device_ms_per_step", "kda_decode_hbm_roofline"])
def test_a_trace_reader_returns_nothing_on_an_untraced_run(record, name):
    assert report.read_metric(name, record) is None


def test_weights_in_blocks_and_a_layer_at_a_time_are_the_stacked_leaves():
    from benchmarks.families import olmo_hybrid as fam
    from benchmarks.reference import olmo_hybrid as ref
    config = bench_toy_olmo.cell().config
    big = 2 ** 31 + 12345
    w = fam.weights(config, trees.key_from_seed(big))
    again = fam.weights(config, trees.key_from_seed(big))
    other = fam.weights(config, trees.key_from_seed(big + 1))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(again)))
    assert not np.array_equal(w["embed"], other["embed"])
    kinds = ref.layer_types_of(config)
    assert kinds == ("gdn", "gdn", "gdn", "attention")
    seen = {"gdn": 0, "attention": 0}
    for l, kind in enumerate(kinds):
        got_kind, mixer, mlp = fam.layer_weights(
            config, trees.key_from_seed(big), l)
        assert got_kind == kind
        for got, stack, at in ((mixer, w["layers"][kind], seen[kind]),
                               (mlp, w["layers"]["mlp"], l)):
            want = jax.tree.leaves(jax.tree.map(lambda a: a[at], stack))
            for (path, leaf), ref_leaf in zip(
                    jax.tree_util.tree_flatten_with_path(got)[0], want):
                assert leaf.dtype == np.float32
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(ref_leaf, np.float32),
                    err_msg=f"{l} {jax.tree_util.keystr(path)}")
        seen[kind] += 1
    top = fam.top_weights(config, trees.key_from_seed(big))
    assert set(top) == {"embed", "head", "final_norm"}  # the head is untied
    for name in top:
        np.testing.assert_array_equal(np.asarray(top[name]),
                                      np.asarray(w[name], np.float32))
    # bfloat16 products on the device, float32 the small leaves; the
    # program's own tree has the same leaves and shapes
    from horovod_tpu.models import olmo_hybrid as oh
    mine = {jax.tree_util.keystr(p): (a.shape, str(a.dtype)) for p, a in
            jax.tree_util.tree_flatten_with_path(w)[0]}
    theirs = jax.eval_shape(lambda: oh.init_params(
        fam.program_config(config), jax.random.PRNGKey(0)))
    assert mine == {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
                    for p, a in
                    jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert mine["['layers']['gdn']['w_qkv']"] == ((3, 64, 384), "bfloat16")
    assert mine["['layers']['gdn']['A_log']"] == ((3, 4), "float32")
    assert mine["['layers']['attention']['q_norm']"] == ((1, 64), "float32")
    assert mine["['layers']['mlp']['w_down']"] == ((4, 96, 64), "bfloat16")

    # the draws (the configuration file's ``assumed``)
    def std(leaf):
        return float(np.std(np.asarray(leaf, np.float32)))
    m = w["layers"]["gdn"]
    assert std(w["embed"]) == pytest.approx(fam.EMBED_DEVIATION, rel=0.05)
    assert std(w["head"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(m["w_qkv"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(m["w_a"]) == pytest.approx(0.125 * 64 ** -0.5, rel=0.12)
    assert std(m["conv_w"]) == pytest.approx(0.5, rel=0.1)
    assert std(w["layers"]["mlp"]["norm"]) == pytest.approx(0.1, rel=0.2)
    a = np.exp(np.asarray(m["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(m["dt_bias"])))       # softplus
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01


def test_the_program_config_is_the_files():
    from benchmarks.families import olmo_hybrid as fam
    cfg = fam.program_config(_config())
    assert cfg.layer_types == ("gdn",) * 3 + ("attention",) \
        + ("gdn",) * 3 + ("attention",)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (3840, 30, 30, 128, 11008, 100352)
    assert (cfg.gdn_n_heads, cfg.gdn_d_key, cfg.gdn_d_value, cfg.gdn_conv,
            cfg.norm_eps) == (30, 96, 192, 4, 1e-6)
    assert cfg.post_norm and not cfg.has_experts
    assert cfg.runs() == [("gdn", 0, 0, 3), ("attention", 3, 0, 1),
                          ("gdn", 4, 3, 3), ("attention", 7, 1, 1)]


def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog row's ``config`` under its own key, nested
    groups whole, but for the two cuts ``reduced`` names."""
    config = _config()
    # allenai/Olmo-Hybrid-7B config.json as the catalog reads it
    types = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": types * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    cut = {"num_hidden_layers": 8, "layer_types": types * 2}
    assert config["reduced"] == list(cut)
    assert config["published"] == {k: published[k] for k in cut}
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    for key in ("deployment", "assumed", "served_dtype"):
        assert config[key], key
    assert "4 pipeline stages of 8 layers" in config["deployment"]
    assert "float32 delta-rule state" in config["served_dtype"]
    for key in ("layer", "gdn", "gdn_qkv", "gdn_init", "attention",
                "state_dtype", "weights"):
        assert key in config["assumed"], key
    for reading in ("post", "silu and not sigmoid", "NoPE", "whole 3840",
                    "then q and k L2-normalised per head"):
        assert reading in json.dumps(config["assumed"]), reading
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(c for c in spec["configs"] if c["name"] == "olmo_hybrid_7b")
    configuration_rule(entry, config)
    # a cut's floors: a whole period in its published ratio, the whole
    # vocabulary
    assert config["layer_types"][:4] == types
    from benchmarks.roofline import olmo_hybrid as cost
    p = cost.parameters(config)
    assert p["gdn"] + p["gdn_small"] == pytest.approx(88.750e6, rel=1e-4)
    assert p["mlp"] == 3 * 3840 * 11008                 # 126.812 M
    assert p["gdn"] + p["gdn_small"] + p["mlp"] + p["mlp_small"] \
        == pytest.approx(215.570e6, rel=1e-4)
    assert p["attention"] + p["attention_small"] + p["mlp"] \
        + p["mlp_small"] == pytest.approx(185.810e6, rel=1e-4)
    held = (6 * (p["gdn"] + p["gdn_small"])
            + 2 * (p["attention"] + p["attention_small"])
            + 8 * (p["mlp"] + p["mlp_small"]) + 2 * p["head"]
            + p["final_norm"])
    assert held == pytest.approx(2.436e9, rel=1e-3)     # 4.871 GB in bf16
    whole = {**config, **published}
    assert cost.layer_counts(whole) == {"gdn": 24, "attention": 8, "all": 32}
    assert 24 * (p["gdn"] + p["gdn_small"]) + 8 * (
        p["attention"] + p["attention_small"]) + 32 * (
        p["mlp"] + p["mlp_small"]) + 2 * p["head"] == pytest.approx(
        7.431e9, rel=1e-3)
    # 64 slots x 6 layers x (30 x 96 x 192 + 3 x 11520) x 4 B
    assert 64 * 6 * cost.slot_state_numbers(config) * 4 == pytest.approx(
        0.902e9, rel=1e-3)
    cell = cells.load_cell(CELL)
    engine, t = cell.traffic["engine"], cell.traffic
    assert (t["clients"], engine["slots"], engine["max_seq"], engine["page"],
            engine["prefill_chunk"], engine["prefix_cache"]) == (
                64, 64, 3072, 128, 256, False)
    assert (t["prompt_len"], t["output_len"], t["strata"]) == (
        {"dist": "loguniform", "lo": 512, "hi": 2560},
        {"dist": "uniform", "lo": 128, "hi": 512}, 8)
    assert t["check_pad_to"] == engine["max_seq"]
    from benchmarks.kinds import serve_closed
    prompts = serve_closed.quantile_lengths(t["prompt_len"], 8)
    assert prompts == [566, 692, 847, 1035, 1266, 1548, 1893, 2315]
    # 2-10 chunks of 256, 5.6 in the mean: ~0.82 of chunks carried
    chunks = [-(-n // 256) for n in prompts]
    assert (min(chunks), max(chunks), sum(chunks) / 8) == (3, 10, 5.625)
    assert serve_closed.quantile_lengths(t["output_len"], 8) == list(
        range(152, 489, 48))
    assert cell.chips == 1 and "limits_why" in t
    assert t["limits"]["served_logit_gap"] > 0


def test_the_cell_lists_the_serve_readers_and_its_own_alone():
    cell = cells.load_cell(CELL)
    mine = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= mine
    assert SHARED_SERVE_READERS | {"setup_compile_s", "compiles_in_window"} \
        <= mine
    assert not {m for m in mine if m.startswith(
        ("moe_", "ssm_", "kda_", "mla_", "kimi_", "paged_decode_"))}
    assert not mine & {"decode_hbm_roofline", "prefill_ms_per_prompt_token"}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == "serve_out_tokens_per_s"
    assert spec["workloads"][-1]["name"] == CELL
    assert spec["configs"][-1]["name"] == "olmo_hybrid_7b"
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "pythia410m_train_dp4"]


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
        assert report.read_metric(f"gdn_ms_per_decode_step.{part}", rec) \
            == pytest.approx(ms), part
    for part, ms in (("proj", 3.0), ("conv", 0.3), ("scan", 2.5),
                     ("gate", 0.1)):
        assert report.read_metric(f"gdn_ms_per_prefill_chunk.{part}", rec) \
            == pytest.approx(ms), part
    assert report.read_metric("gdn_chunks_carried_share", rec) \
        == pytest.approx(280 / 340)
    assert report.read_metric("gdn_state_gb", rec) == pytest.approx(0.90243)
    # a step of 2 live slots of 64 holding 300 and 500 keys: 6 GDN mixers of
    # 88.70 M products (their 50.2 k small leaves in float32), 2 attention
    # mixers of 58.98 M (11.5 k small), 8 SwiGLUs of 126.8 M (3840 small),
    # the head's 385.4 M and the final norm, 64 embedding rows, 800 keys of
    # 2 x 30 x 128 numbers in 2 layers, in bfloat16; 2 slots' state of 6 x
    # (552 960 + 34 560) float32 numbers read and written; at 819 GB/s, over
    # 25 ms
    bytes_ = (6 * (88_704_000 * 2 + 50_172 * 4)
              + 2 * (58_982_400 * 2 + 11_520 * 4)
              + 8 * (126_812_160 * 2 + 3840 * 4)
              + 385_351_680 * 2 + 3840 * 4 + 64 * 3840 * 2
              + 2 * 800 * 2 * 30 * 128 * 2 + 2 * 6 * 2 * 587_520 * 4)
    assert report.read_metric("gdn_decode_hbm_roofline", rec) \
        == pytest.approx(100 * bytes_ / 819e9 / 0.025)
    # 1.2 TFLOP a chunk at 197 TFLOP/s over 150 ms
    assert report.read_metric("gdn_prefill_flops_roofline", rec) \
        == pytest.approx(100 * 1.2e12 / 197e12 / 0.15)
    for name in ("gdn_decode_hbm_roofline", "gdn_prefill_flops_roofline"):
        assert 0 < report.read_metric(name, rec) <= 100
    # the GDN scopes are in ``.other`` of the decode step's split
    assert report.read_metric("scope_ms_per_decode_step.other", rec) \
        == pytest.approx(1e3 * (0.044 + 0.008 + 0.001 + 0.024 + 0.0004) / 4)
    # another family's model gives the rooflines nothing to read
    rec.program["model"] = {"moe_topk": 12}
    assert report.read_metric("gdn_decode_hbm_roofline", rec) is None
    assert report.read_metric("gdn_prefill_flops_roofline", rec) is None


def test_the_rooflines_count_what_the_chip_must_do():
    from benchmarks.roofline import olmo_hybrid as cost
    config = _config()
    p = cost.parameters(config)
    # one token at position 999: every product twice, the rule's 6 x 30 x
    # 96 x 192 a GDN layer, 1000 keys of 30 heads x (128 + 128) in 2 layers,
    # the head
    rule = 6 * 30 * 96 * 192
    one = cost.forward_flops(config, 1, 999)
    assert one == pytest.approx(
        2 * (6 * p["gdn"] + 2 * p["attention"] + 8 * p["mlp"] + p["head"])
        + 6 * rule + 2 * 2 * 30 * 256 * 1000)
    # a chunk is causal: 256 new tokens after 1000 see 1000 + 1..256 keys;
    # no logits but the chunk's last row when asked
    chunk = cost.forward_flops(config, 256, 1000, logit_rows=0)
    assert chunk == pytest.approx(
        256 * (2 * (6 * p["gdn"] + 2 * p["attention"] + 8 * p["mlp"])
               + 6 * rule) + 2 * 2 * 30 * 256 * (256 * 1000 + 256 * 257 // 2))
    # ~0.86 TFLOP a chunk of 256 at the prompts' middle
    assert chunk == pytest.approx(0.875e12, rel=0.02)
    # a decode step: the weights once but the embedding, the rows' embedding,
    # the live K/V rows of 2 layers, the live slots' state read and written
    b = cost.decode_step_bytes(config, rows=64, cached_tokens=100_000,
                               live_slots=60)
    weights = (6 * (p["gdn"] * 2 + p["gdn_small"] * 4)
               + 2 * (p["attention"] * 2 + p["attention_small"] * 4)
               + 8 * (p["mlp"] * 2 + p["mlp_small"] * 4) + p["head"] * 2
               + 3840 * 4)
    assert weights == pytest.approx(4.10e9, rel=0.01)
    assert b == pytest.approx(
        weights + 64 * 3840 * 2 + 2 * 100_000 * 2 * 3840 * 2
        + 2 * 6 * 60 * (30 * 96 * 192 + 3 * 11520) * 4)
