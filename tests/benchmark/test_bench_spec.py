"""``BENCHMARK.json`` against the contract, the files it names, and the
readers of its per-layer metrics on a made-up run: a cell, a configuration, a
traffic mix and a per-layer metric are each found by name, so a later PR adds
one by adding files and an entry."""

import json
import os
import re
import shutil

import pytest

from benchmarks.lib import cell as cells, report, trace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    # a full check with all 24 cells has to fit
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert spec["command"][-1].startswith(spec["paths"][0] + "/")
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_names_units_and_keys(spec):
    for entry in spec["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
        assert len(entry["source"]) <= 200
        assert any(entry["file"].startswith(p + "/") for p in spec["paths"])
    for entry in spec["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
        assert 1 <= len(entry["why"]) <= 200 and "\t" not in entry["why"]
    for entry in spec["end_to_end"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "bound", "source"}
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    for entry in spec["per_layer"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "source", "layer", "moves"}
        assert entry["source"] in SOURCES
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in names


def test_cells_and_what_they_report(spec):
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        c = cells.load_cell(w["name"])
        mine = {m["name"] for m in c.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert c.per_layer, w["name"]
        for m in c.per_layer:
            assert m["moves"] in mine, (w["name"], m["name"])
        assert "limits" in c.traffic and c.traffic["kind"]
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "kinds", c.traffic["kind"] + ".py"))
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "families", c.config["family"] + ".py"))
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in spec["workloads"]}
    # a kernel's roofline stands beside a whole-step share of the peak that
    # moves the same metric
    for m in spec["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in spec["per_layer"]), m["name"]


UNCUT = ("pythia410m", "resnet50")      # run at the source's own sizes


def configuration_rule(entry, config, root=cells.ROOT):
    """What a configuration's file has to state beside its sizes. A cut one
    (``reduced`` not empty) names only keys it has, gives the source's value
    of each under ``published`` and says under ``deployment`` over how many
    chips a layer is shared and how."""
    assert config["reduced"] == entry["reduced"]
    if entry["name"] in UNCUT:
        assert config["reduced"] == []
    if config["reduced"]:
        assert set(config["published"]) == set(config["reduced"])
        for key in config["reduced"]:
            assert key in config, key
            assert config["published"][key] != config[key], key
        assert isinstance(config["deployment"], str)
        assert re.search(r"\d+ chips?\b", config["deployment"]), \
            "over how many chips a layer is shared"
    assert config["source"].split(",")[0] in entry["source"] \
        or entry["source"] in config["source"]
    assert "assumed" in config and "family" in config
    # the plain reference the cell's outputs are compared with
    assert config["reference"] == \
        f"benchmarks/reference/{config['family']}.py"
    assert os.path.exists(os.path.join(root, config["reference"]))


def test_configuration_files(spec):
    assert set(UNCUT) <= {entry["name"] for entry in spec["configs"]}
    for entry in spec["configs"]:
        configuration_rule(entry, cells.load_json(
            os.path.join(cells.ROOT, entry["file"])))
    lm = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "pythia410m.json"))
    # EleutherAI/pythia-410m config.json
    assert (lm["hidden_size"], lm["num_hidden_layers"],
            lm["num_attention_heads"], lm["intermediate_size"],
            lm["vocab_size"], lm["max_position_embeddings"]) == (
                1024, 24, 16, 4096, 50304, 2048)


@pytest.mark.parametrize("changes, ok", [
    pytest.param({}, True, id="cut_and_stated"),
    pytest.param({"published": {}}, False, id="published_lacks_the_key"),
    pytest.param({"published": {"num_hidden_layers": 4}}, False,
                 id="published_equals_what_is_run"),
    pytest.param({"deployment": None}, False, id="no_deployment"),
    pytest.param({"deployment": "cut to fit"}, False,
                 id="deployment_names_no_chips"),
    pytest.param({"reduced": ["num_layers"],
                  "published": {"num_layers": 24}}, False,
                 id="reduced_names_a_key_the_file_lacks"),
    pytest.param({"reduced": [], "published": {}}, False,
                 id="file_and_entry_disagree"),
])
def test_a_cut_configuration_states_what_was_cut(changes, ok):
    lm = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "pythia410m.json"))
    config = {
        **lm, "num_hidden_layers": 4, "reduced": ["num_hidden_layers"],
        "published": {"num_hidden_layers": 24},
        "deployment": "one of 8 chips that share each layer: attention "
                      "replicated, the MLP split by columns", **changes}
    entry = {"name": "pythia410m_cut", "source": lm["source"],
             "reduced": ["num_hidden_layers"]}
    if ok:
        configuration_rule(entry, config)
    else:
        with pytest.raises((AssertionError, KeyError, TypeError)):
            configuration_rule(entry, config)
    # the two that run at the source's sizes may not be cut
    with pytest.raises(AssertionError):
        configuration_rule({**entry, "name": "pythia410m"}, config)


def every_metric_has_its_own_reader(spec, metrics):
    """``metrics/<name>.py``, or for a metric split by suffix its stem's."""
    for m in spec["per_layer"]:
        own = os.path.join(metrics, m["name"] + ".py")
        stem = os.path.join(metrics, m["name"].rsplit(".", 1)[0] + ".py")
        assert os.path.exists(own) or os.path.exists(stem), own
    # no reader that no metric names
    named = {m["name"] for m in spec["per_layer"]}
    named |= {n.rsplit(".", 1)[0] for n in named}
    for f in os.listdir(metrics):
        if f.endswith(".py"):
            assert f[:-3] in named, f


def test_every_metric_has_its_own_reader(spec):
    every_metric_has_its_own_reader(
        spec, os.path.join(cells.BENCH_DIR, "metrics"))


def test_a_split_metric_is_read_by_its_own_file_before_its_stem(spec):
    rec = _made_up_run("pythia410m_serve_closed")
    # mfu.serve.py counts the served tokens' operations, mfu.py a train step's
    assert report.read_metric("mfu.serve", rec) == pytest.approx(
        100 * 5e12 / 1.15 / 197e12)
    rec = _made_up_run("resnet50_train_1chip")
    assert report.read_metric("mfu.cnn", rec) == \
        report.read_metric("mfu.lm", rec)
    with pytest.raises(FileNotFoundError):
        report.read_metric("no_such_metric.lm", rec)


def test_no_end_to_end_metric_stands_on_a_saturated_tail(spec):
    """The closed loop is at capacity by construction: its times to first
    token are per-layer readings (PERF.md, Open questions)."""
    serve = cells.load_cell("pythia410m_serve_closed")
    assert {m["name"] for m in serve.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    assert "serve_ttft_p50_ms" in {m["name"] for m in serve.per_layer}


# host spans of two scheduling cycles as the program writes them (name,
# start, seconds, index of the enclosing span): the first prefills nothing,
# so a reader of prefill spans brings its own in its ``example``
SPANS = [
    ("bench.schedule", 0.000, 0.0510, -1),
    ("hvd.serve.cycle", 0.0005, 0.0500, 0),
    ("hvd.serve.retire", 0.0005, 0.0010, 1),
    ("hvd.serve.admit", 0.0015, 0.0020, 1),
    ("hvd.serve.prefill", 0.0040, 0.0100, 1),
    ("hvd.serve.decode", 0.0140, 0.0365, 1),
    ("bench.decode", 0.0141, 0.0360, 5),
    ("hvd.engine.decode.dispatch", 0.0142, 0.0015, 6),
    ("hvd.engine.decode.wait", 0.0157, 0.0300, 6),
    ("bench.schedule", 0.0600, 0.0405, -1),
    ("hvd.serve.cycle", 0.0602, 0.0400, 9),
    ("hvd.serve.decode", 0.0603, 0.0390, 10),
    ("bench.decode", 0.0604, 0.0380, 11),
    ("hvd.engine.decode.dispatch", 0.0605, 0.0013, 12),
    ("hvd.engine.decode.wait", 0.0618, 0.0340, 12),
]
# own device seconds by program, scope path and operation
SCOPES = {
    "jit_hvd_serve_decode": {
        "hvd_attention": {"hvd_paged_decode": 0.016, "fusion": 0.004},
        "hvd_mlp": {"fusion": 0.028},
        "hvd_kv_write": {"fusion": 0.006},
        trace.UNSCOPED: {"convert": 0.030, "fusion": 0.012, "while": 0.002}},
    "jit_train_step": {
        "hvd_attention": {"hvd_flash_fwd": 0.1, "hvd_flash_bwd_dq": 0.11,
                          "hvd_flash_bwd_dkv": 0.14},
        "hvd_mlp": {"fusion": 0.30},
        "hvd_loss": {"fusion": 0.05},
        "hvd_optimizer/hvd_unfused_apply": {"fusion": 0.04},
        trace.UNSCOPED: {"fusion": 0.2, "copy": 0.1}},
}


def _made_up_run(workload, root=cells.ROOT):
    c = cells.load_cell(workload, root=root)
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    rec = report.RunRecord(
        cell=c, seed=1, peak=peak,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": c.chips})
    rec.setup = {"setup_s": 20.0, "compile_s": 4.0}
    rec.unit_s = [0.28, 0.29, 0.28, 0.30]
    rec.elapsed_s = sum(rec.unit_s)
    rec.program = {
        "argument_bytes": 3_241_000_000, "temp_bytes": 7_866_000_000,
        "required_flops_per_step": 2.4231e9 * 8192 * c.chips,
        "shapes": {"batch": 4, "seq": 2048, "heads": 16, "head_dim": 64,
                   "layers": 24},
        "hlo_text": "%ar = f32[1000]{0} all-reduce(%g), replica_groups={}",
        "heads": 16, "head_dim": 64, "layers": 24}
    rec.counters = {
        "steps_in_trace": 4, "ttft_s": [0.2, 0.3, 0.4],
        "decode_keys": [[300, 500]] * 4, "prefill_tokens": 2000,
        "required_flops": 5e12, "batch_occupancy": 0.9}
    calls = 24 * 4
    decode = ("jit_hvd_serve_decode", 0.025,
              frozenset({"fusion", "hvd_paged_decode"}))
    prefill = ("jit_hvd_serve_prefill", 0.15, frozenset({"fusion", "copy"}))
    step = ("jit_train_step", 0.28, frozenset({"fusion", "hvd_flash_fwd"}))
    rec.trace = trace.Summary(
        window_s=1.2, busy_s=1.19, devices=c.chips,
        op_self_s={}, idle_gaps=[], exposed_collective_s=0.16,
        op_total_s={"hvd_flash_fwd": 0.1, "hvd_flash_bwd_dq": 0.11,
                    "hvd_flash_bwd_dkv": 0.14, "hvd_paged_decode": 0.004},
        op_calls={"hvd_flash_fwd": calls, "hvd_flash_bwd_dq": calls,
                  "hvd_flash_bwd_dkv": calls, "hvd_paged_decode": calls},
        programs=[decode, prefill] * 2 + [decode] * 2 + [step] * 4,
        spans=[trace.Span(*row) for row in SPANS],
        scope_op_s=json.loads(json.dumps(SCOPES)))
    return rec


def readers_read(spec, root=cells.ROOT):
    """Every per-layer metric of every cell reads something on the made-up
    run, once its reader's ``example`` (where it has one) has added to the
    run what that reader reads; a share reads in (0, 100]."""
    metrics_dir = os.path.join(root, "benchmarks", "metrics")
    for w in spec["workloads"]:
        rec = _made_up_run(w["name"], root=root)
        for m in rec.cell.per_layer:
            module, _ = report.load_reader(m["name"], metrics_dir)
            if hasattr(module, "example"):
                module.example(rec)
            value = report.read_metric(m["name"], rec, metrics_dir)
            assert value is not None, (w["name"], m["name"])
            if m["unit"] == "%":
                assert 0 < value <= 100, (m["name"], value)


def test_readers_on_a_made_up_run(spec):
    readers_read(spec)
    rec = _made_up_run("pythia410m_train_1chip")
    assert report.read_metric("step_ms_p50.lm", rec) == pytest.approx(285.0)
    assert report.read_metric("step_hbm_gb.lm", rec) == pytest.approx(11.107)
    # 4 steps of 19.85 TFLOP in 1.15 s on one 197 TFLOP/s chip
    assert report.read_metric("mfu.lm", rec) == pytest.approx(
        100 * 4 * 2.4231e9 * 8192 / 1.15 / 197e12)
    # forward: 96 calls of 34.38 GFLOP / 197 TFLOP/s over 0.1 s
    assert report.read_metric("flash_fwd_roofline", rec) == pytest.approx(
        100 * 96 * 34_376_515_584 / 197e12 / 0.1)
    assert report.read_metric("flash_ms_per_step", rec) == pytest.approx(
        1e3 * 0.35 / 4)


def test_a_reader_with_nothing_to_read_returns_nothing(spec):
    rec = _made_up_run("pythia410m_train_1chip")
    rec.trace = None
    assert report.read_metric("flash_fwd_roofline", rec) is None
    assert report.read_metric("flash_ms_per_step", rec) is None
    rec = _made_up_run("pythia410m_train_dp4")
    rec.trace.exposed_collective_s = 0.0
    rec.program["hlo_text"] = ""
    assert report.read_metric("allreduce_exposed_ms_per_step", rec) is None
    assert report.read_metric("allreduce_bytes_per_step", rec) is None
    rec = _made_up_run("pythia410m_serve_closed")
    rec.trace.op_total_s = {}
    assert report.read_metric("paged_decode_roofline", rec) is None


COUNTER_READER = '''"""Tokens the router sent to the experts this chip holds, per decode step."""


def read(run):
    steps = len(run.unit_s)
    routed = run.counters.get("routed_tokens")
    return routed / steps if routed and steps else None


def example(run):
    run.counters["routed_tokens"] = 4 * 16 * 12
'''
SCOPE_READER = '''"""Own device milliseconds of the expert block per decode step."""
from benchmarks.lib import readers

SCOPES = {"experts": "hvd_experts", "router": "hvd_router"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, "hvd_serve_decode", SCOPES, part)


def example(run):
    decode = run.trace.scope_op_s["jit_hvd_serve_decode"]
    decode["hvd_mlp/hvd_experts"] = {"fusion": 0.020}
    decode["hvd_mlp/hvd_router"] = {"fusion": 0.002}
'''


def test_a_cell_is_added_as_files_and_one_entry(spec, tmp_path):
    """A cut configuration with a family of its own, a traffic mix, a cell, a
    metric on a counter of that family and one on a scope of its program:
    new files and new entries (and the cell's name appended to ``workloads``
    lists); nothing that is there changes, and the rules above hold of the
    new tree."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics", "reference"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub),
                        root / "benchmarks" / sub)
    new = json.loads(json.dumps(spec))
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "pythia410m.json"))
    config.update(
        family="toy_moe", reference="benchmarks/reference/toy_moe.py",
        num_hidden_layers=16, n_routed_experts=16,
        reduced=["num_hidden_layers", "n_routed_experts"],
        published={"num_hidden_layers": 28, "n_routed_experts": 512},
        deployment="one of 32 chips that share each layer: attention and the "
                   "dense MLPs replicated, 16 of 512 experts here")
    (root / "benchmarks/configs/other.json").write_text(json.dumps(config))
    (root / "benchmarks/reference/toy_moe.py").write_text("")
    traffic = cells.load_json(os.path.join(
        cells.BENCH_DIR, "traffic", "serve_closed_c16.json"))
    traffic["clients"] = 8
    (root / "benchmarks/traffic/serve_closed_c8.json").write_text(
        json.dumps(traffic))
    (root / "benchmarks/metrics/routed_tokens_per_step.py").write_text(
        COUNTER_READER)
    (root / "benchmarks/metrics/moe_ms_per_decode_step.py").write_text(
        SCOPE_READER)
    new["configs"].append({
        "name": "other", "source": config["source"],
        "reduced": config["reduced"], "why": "y",
        "file": "benchmarks/configs/other.json"})
    new["workloads"].append({"name": "other_serve", "config": "other",
                             "traffic": "serve_closed_c8", "chips": 1,
                             "why": "z"})
    rate = next(m for m in new["end_to_end"]
                if m["name"] == "serve_out_tokens_per_s")
    rate["workloads"].append("other_serve")
    # an existing metric is taken into the new cell by its name, appended
    next(m for m in new["per_layer"]
         if m["name"] == "mfu.serve")["workloads"].append("other_serve")
    for name, source in (("routed_tokens_per_step", "program_counter"),
                         ("moe_ms_per_decode_step.experts", "device_trace"),
                         ("moe_ms_per_decode_step.router", "device_trace")):
        new["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower", "source": source,
            "layer": "serve", "moves": "serve_out_tokens_per_s",
            "workloads": ["other_serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    c = cells.load_cell("other_serve", root=str(root))
    assert c.config["num_hidden_layers"] == 16
    assert c.traffic["clients"] == 8
    assert {m["name"] for m in c.end_to_end} == {"serve_out_tokens_per_s",
                                                 "setup_s"}
    mine = {m["name"] for m in c.per_layer}
    assert {"routed_tokens_per_step", "moe_ms_per_decode_step.experts",
            "mfu.serve"} <= mine
    assert "decode_step_ms_p50" not in mine     # lists other cells only
    # metrics without a cell list follow every cell that reports what they
    # move
    assert "setup_compile_s" in mine
    # the rules of this file, held of the new tree
    test_names_units_and_keys(new)
    for entry in new["configs"]:
        configuration_rule(entry, cells.load_json(
            root / entry["file"]), root=str(root))
    every_metric_has_its_own_reader(new, str(root / "benchmarks/metrics"))
    readers_read(new, root=str(root))
    rec = _made_up_run("other_serve", root=str(root))
    metrics_dir = str(root / "benchmarks/metrics")
    # nothing to read until the family counts and the program has the scope
    for name in ("routed_tokens_per_step", "moe_ms_per_decode_step.experts"):
        assert report.read_metric(name, rec, metrics_dir) is None
        report.load_reader(name, metrics_dir)[0].example(rec)
    assert report.read_metric("routed_tokens_per_step", rec, metrics_dir) \
        == 4 * 12 * 16 / 4
    # 20 ms over the 4 decode runs of the made-up window
    assert report.read_metric("moe_ms_per_decode_step.experts", rec,
                              metrics_dir) == pytest.approx(5.0)
    assert report.read_metric("moe_ms_per_decode_step.router", rec,
                              metrics_dir) == pytest.approx(0.5)
