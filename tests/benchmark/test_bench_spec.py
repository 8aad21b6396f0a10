"""``BENCHMARK.json`` against the contract, the files it names, and the
readers of its per-layer metrics on a made-up run: a cell, a configuration, a
traffic mix and a per-layer metric are each found by name, so a later PR adds
one by adding files and an entry."""

import json
import os
import re

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


def test_configuration_files(spec):
    for entry in spec["configs"]:
        config = cells.load_json(os.path.join(cells.ROOT, entry["file"]))
        assert config["reduced"] == entry["reduced"] == []
        assert config["source"].split(",")[0] in entry["source"] \
            or entry["source"] in config["source"]
        assert "assumed" in config and "family" in config
        # the plain reference the cell's outputs are compared with
        assert config["reference"] == \
            f"benchmarks/reference/{config['family']}.py"
        assert os.path.exists(os.path.join(cells.ROOT, config["reference"]))
    lm = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "pythia410m.json"))
    # EleutherAI/pythia-410m config.json
    assert (lm["hidden_size"], lm["num_hidden_layers"],
            lm["num_attention_heads"], lm["intermediate_size"],
            lm["vocab_size"], lm["max_position_embeddings"]) == (
                1024, 24, 16, 4096, 50304, 2048)


def test_every_metric_has_its_own_reader(spec):
    """``metrics/<name>.py``, or for a metric split by suffix its stem's."""
    metrics = os.path.join(cells.BENCH_DIR, "metrics")
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


def _made_up_run(workload):
    c = cells.load_cell(workload)
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
    rec.trace = trace.Summary(
        window_s=1.2, busy_s=1.19, devices=c.chips,
        op_self_s={}, idle_gaps=[], exposed_collective_s=0.16,
        op_total_s={"hvd_flash_fwd": 0.1, "hvd_flash_bwd_dq": 0.11,
                    "hvd_flash_bwd_dkv": 0.14, "hvd_paged_decode": 0.004},
        op_calls={"hvd_flash_fwd": calls, "hvd_flash_bwd_dq": calls,
                  "hvd_flash_bwd_dkv": calls, "hvd_paged_decode": calls},
        programs=[("jit__unknown", 0.3, frozenset({"fusion", "copy"})),
                  ("jit__unknown", 0.1, frozenset({"hvd_paged_decode"}))])
    return rec


def test_readers_on_a_made_up_run(spec):
    for w in spec["workloads"]:
        rec = _made_up_run(w["name"])
        for m in rec.cell.per_layer:
            value = report.read_metric(m["name"], rec)
            assert value is not None, (w["name"], m["name"])
            if m["unit"] == "%":
                assert 0 < value <= 100, (m["name"], value)
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


def test_a_cell_is_added_as_files_and_one_entry(spec, tmp_path):
    """A new configuration, traffic mix, cell and metric: new files and new
    entries; nothing that is there changes."""
    root = tmp_path
    for sub in ("configs", "traffic"):
        os.makedirs(root / "benchmarks" / sub)
    new = json.loads(json.dumps(spec))
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "pythia410m.json"))
    config["num_hidden_layers"] = 16
    (root / "benchmarks/configs/other.json").write_text(json.dumps(config))
    traffic = cells.load_json(os.path.join(
        cells.BENCH_DIR, "traffic", "lm_train_s2048.json"))
    traffic["seq_len"] = 1024
    (root / "benchmarks/traffic/lm_train_s1024.json").write_text(
        json.dumps(traffic))
    new["configs"].append({"name": "other", "source": "x", "reduced": [],
                           "file": "benchmarks/configs/other.json",
                           "why": "y"})
    new["workloads"].append({"name": "other_train", "config": "other",
                             "traffic": "lm_train_s1024", "chips": 1,
                             "why": "z"})
    new["end_to_end"][0]["workloads"].append("other_train")
    new["per_layer"].append({
        "name": "new_metric", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "model",
        "moves": "tokens_per_s_per_chip", "workloads": ["other_train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    c = cells.load_cell("other_train", root=str(root))
    assert c.config["num_hidden_layers"] == 16
    assert c.traffic["seq_len"] == 1024
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_per_chip",
                                                 "setup_s"}
    assert "new_metric" in {m["name"] for m in c.per_layer}
    # metrics without a cell list follow every cell that reports what they
    # move
    assert "setup_compile_s" in {m["name"] for m in c.per_layer}
