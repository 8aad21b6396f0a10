"""CPU rehearsal of the harness: every kind of cell at toy width runs the
one command's code end to end in-process, and without the rehearsal switch
the command refuses to measure off the chip."""

import json

import pytest

import bench_toy

KEYS = {"correct", "attempted", "failed", "metrics", "device", "window",
        "compared"}


@pytest.fixture(autouse=True)
def interpret_kernels(monkeypatch, tmp_path):
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")


@pytest.mark.parametrize("name", sorted(bench_toy.CELLS))
def test_cell_runs_end_to_end(name):
    line = bench_toy.rehearse(name)
    assert set(line) == KEYS
    assert list(line)[-1] == "compared"         # comes last in the line
    json.dumps(line)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = bench_toy.cell(name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == cell.chips
    for value, limit in line["compared"].values():
        assert value <= limit
    # an untraced run measures what it was asked to
    assert line["window"]["asked_s"] == 0.3 <= line["window"]["ran_s"]


@pytest.mark.parametrize("name, method, kinds_own", [
    ("serve_closed", "request", "ttft_s"),
    ("lm_train_1", "step", "steps_in_trace"),
])
def test_a_familys_counter_arrives_as_the_windows_difference(
        monkeypatch, name, method, kinds_own):
    import importlib
    family = importlib.import_module(
        "benchmarks.families." + bench_toy.cell(name).config["family"])
    program_cls = (family.ServeProgram if name.startswith("serve")
                   else family.TrainProgram)
    bench_toy.count_calls(monkeypatch, program_cls, method, "toy_calls")
    rec = bench_toy.record(name)
    added = rec.counters["toy_calls"]
    # set-up made calls of its own (warm rotation; check and warm steps)
    assert rec.counters["toy_calls_at"] == list(range(
        rec.counters["toy_calls_at"][0], rec.counters["toy_calls_at"][0]
        + added))
    assert rec.counters["toy_calls_at"][0] > 0 and added > 0
    if name.startswith("serve"):
        # one request sent for each that finished inside the window
        assert added == rec.attempted
        # the family's own arrive the same way, beside the kind's
        assert len(rec.counters["decode_keys"]) == len(rec.unit_s)
        assert {"ttft_s", "batch_occupancy", "prefill_tokens",
                "required_flops"} <= set(rec.counters)
    else:
        assert added == rec.counters["steps_in_trace"]
    # a counter under a name the kind uses itself is refused
    bench_toy.count_calls(monkeypatch, program_cls, method, kinds_own)
    with pytest.raises(ValueError, match=kinds_own):
        bench_toy.record(name)
    # the run was refused before its window: put away what it had built
    import horovod_tpu as hvd
    from horovod_tpu import serving
    serving.reset_for_tests()
    hvd.shutdown()


def test_same_seed_same_inputs():
    import jax
    import numpy as np
    from benchmarks.families import transformer_lm as lm
    from benchmarks.kinds import serve_closed
    from benchmarks.lib import trees
    cell = bench_toy.cell("serve_closed")
    big = 2 ** 31 + 12345                       # more than 32 signed bits
    a = serve_closed.Clients(cell.traffic, 256, big)
    b = serve_closed.Clients(cell.traffic, 256, big)
    c = serve_closed.Clients(cell.traffic, 256, big + 1)
    first = [a.next(0) for _ in range(8)]
    again = [b.next(0) for _ in range(8)]
    other = [c.next(0) for _ in range(8)]
    assert all(np.array_equal(p, q) and m == n
               for (p, m), (q, n) in zip(first, again))
    # another seed: the same sizes in the same order, other token ids
    assert [(len(p), n) for p, n in first] == [(len(p), n) for p, n in other]
    assert not any(np.array_equal(p, q)
                   for (p, _), (q, _) in zip(first, other))
    # one cycle covers every stratum of both lengths once
    strata = cell.traffic["strata"]
    assert len({len(p) for p, _ in first[:strata]}) == strata
    assert len({n for _, n in first[:strata]}) == strata
    w1 = lm.weights(cell.config, trees.key_from_seed(big))
    w2 = lm.weights(cell.config, trees.key_from_seed(big))
    w3 = lm.weights(cell.config, trees.key_from_seed(big - 2 ** 31))
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(w1), jax.tree.leaves(w2)))
    assert not np.array_equal(w1["embed"], w3["embed"])


def test_no_tpu_no_result(capsys):
    """Without a TPU and without the rehearsal switch the command exits
    non-zero and prints no result line."""
    from benchmarks import run
    rc = run.main(["--workload", "pythia410m_train_1chip", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "not a TPU" in out.err


def test_unknown_workload_is_refused():
    from benchmarks import run
    with pytest.raises(SystemExit):
        run.main(["--workload", "no_such_cell", "--seed", "1",
                  "--seconds", "1"])
