"""chip_smoke.py's control flow, checked before chip time is spent: the
same leg functions at a toy width on the virtual CPU mesh with the Pallas
kernels in interpret mode — plus the guards that keep the script honest
(no TPU -> non-zero exit and no result; importing the entry modules
initialises no backend, so a parent can start children that own the
chips)."""

import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.config import knobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(
    lm=dict(vocab_size=256, d_model=64, n_heads=4, head_dim=16, n_layers=2,
            d_ff=128, max_seq=128, scan_unroll=2, remat=False,
            mlp_recompute=True),
    lm_batch_per_chip=1, lm_seq=128, lm_steps=2,
    resnet="ResNet18", resnet_kwargs=dict(num_filters=8, stage_sizes=[1, 1]),
    image_size=32, resnet_batch_per_chip=2, resnet_steps=4,
    serve=dict(slots=4, page=16, max_seq=128, prefill_chunk=64),
    n_requests=3, prompt_len=20, new_tokens=6,
)


@pytest.fixture()
def interpret_kernels():
    knobs.set_override("HOROVOD_TPU_PALLAS", "interpret")
    yield
    knobs.clear_override("HOROVOD_TPU_PALLAS")


def test_legs_pass_at_toy_width_on_the_virtual_mesh(tmp_path,
                                                    interpret_kernels):
    legs = chip_smoke.run_legs(TOY, on_chip=False,
                               store_dir=str(tmp_path / "store"))
    assert list(legs) == ["start", "eager", "resnet", "lm", "serve"]
    assert all(leg["ok"] for leg in legs.values())
    assert legs["start"]["device_count"] == 8
    assert legs["eager"]["ranks"] == 8
    # the LM step syncs gradients over the whole mesh
    assert 8 in legs["lm"]["allreduce_group_sizes"]
    # interpret mode leaves no Mosaic call: on_chip=True must refuse it
    assert legs["lm"]["flash_kernels"] == {}
    serve = legs["serve"]
    assert serve["default"]["warm_builds"] == 0
    assert serve["default"]["cold_builds"] > 0
    # a replica pinned to the last device booted warm from the store there
    assert serve["last_chip"]["devices"] == [7]
    assert serve["last_chip"]["warm_builds"] == 0
    assert serve["reference_check"]["attention_max_abs_err"] < 2e-3
    json.dumps(legs)                       # the summary line must serialize


def test_missing_kernels_fail_the_lm_leg_on_chip(hvd_ctx):
    """With the kernels off the step takes the jnp path; a chip run must
    fail on that rather than report ok."""
    knobs.set_override("HOROVOD_TPU_PALLAS", "0")
    try:
        with pytest.raises(AssertionError, match="jnp path"):
            chip_smoke.leg_lm(TOY, on_chip=True)
    finally:
        knobs.clear_override("HOROVOD_TPU_PALLAS")


def test_group_size_reads_both_spellings():
    from horovod_tpu.analysis.rules_ir import replica_group_size
    assert replica_group_size("{{0,1,2,3}}") == 4
    assert replica_group_size("{{0,1},{2,3}}") == 2
    assert replica_group_size("[1,4]<=[4]") == 4
    assert replica_group_size("[2,2]<=[4]") == 2
    assert replica_group_size("") == 1


def test_bench_names_its_device_or_fails(monkeypatch):
    """bench.py's measurement paths: an unknown device_kind is an error,
    and no mode picks the CPU on its own — only JAX_PLATFORMS=cpu, the
    CPU by name, runs the CI gates on the virtual mesh."""
    import types

    import bench

    assert bench.peak_flops(
        types.SimpleNamespace(device_kind="TPU v5 lite")) == 197e12
    for kind in ("cpu", "TPU v5 lite pod", None):
        with pytest.raises(ValueError, match="no peak FLOP/s"):
            bench.peak_flops(types.SimpleNamespace(device_kind=kind))

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench.require_chip("test")                 # asked for by name
    assert "device_count=8" in bench._worker_env()["XLA_FLAGS"]
    for value in ("", "tpu,cpu"):              # unset-like / the chip machine
        monkeypatch.setenv("JAX_PLATFORMS", value)
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        with pytest.raises(SystemExit, match="no TPU"):
            bench.require_chip("test")         # this process sits on the CPU
        env = bench._worker_env()
        assert env["JAX_PLATFORMS"] == value and "XLA_FLAGS" not in env


def test_last_stdout_line_is_the_verdict(monkeypatch, tmp_path, capsys):
    """The driver reads the last stdout line and accepts exactly
    ``{"ok", "device": {"platform", "kind", "count"}}``; the per-leg
    summary (ending ``"claim": null``) is the line before it."""
    import types

    import jax

    from horovod_tpu.utils import compile_cache

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip] * 4)
    monkeypatch.setattr(compile_cache, "place", lambda checkout: None)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    seen = {}

    def fake_legs(sizes, on_chip, store_dir):
        seen.update(sizes=sizes, on_chip=on_chip, store_dir=store_dir)
        return {"start": {"ok": True}, "serve": {"ok": seen.get("serve_ok",
                                                               True)}}
    monkeypatch.setattr(chip_smoke, "run_legs", fake_legs)

    assert chip_smoke.main() == 0
    assert seen["sizes"] is chip_smoke.FLAGSHIP and seen["on_chip"] is True
    assert seen["store_dir"].startswith(str(tmp_path))
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    summary = json.loads(lines[-2].split("summary ", 1)[1])
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["legs"]["serve"] == {"ok": True}
    with open(tmp_path / "summary.json") as f:
        assert json.load(f) == summary

    seen["serve_ok"] = False                   # a leg that reports not-ok
    assert chip_smoke.main() == 1
    assert json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])["ok"] is False


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(
        monkeypatch, tmp_path):
    import jax

    from horovod_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/some/dir")
        assert compile_cache.place(str(tmp_path)) == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before  # not in code
        monkeypatch.delenv(compile_cache.ENV)
        want = str(tmp_path / ".jax_cache")
        assert compile_cache.place(str(tmp_path)) == want
        assert compile_cache.place(str(tmp_path)) == want      # fixed path
        assert os.environ[compile_cache.ENV] == want           # children
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.delenv(compile_cache.ENV, raising=False)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""       # no result line off-TPU
    assert "no TPU" in proc.stderr


def test_entry_modules_initialise_no_backend():
    """One process owns the chips: a parent that merely imports the
    package, the benchmark or the launcher must leave JAX's backends
    alone, or its children cannot have the chip."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import horovod_tpu, bench, chip_smoke\n"
        "from horovod_tpu.runner import launch\n"
        "assert launch.main(['--', sys.executable, '-c', 'pass']) == 0\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n" % REPO)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_uncached_compiles_neither_read_nor_write_the_persistent_cache(
        tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from horovod_tpu.utils import compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    entries = lambda: sorted(p.name for p in tmp_path.iterdir()
                             if p.name.endswith("-cache"))
    try:
        for n, v in zip(names, (str(tmp_path), 0.0, 0)):
            jax.config.update(n, v)
        cc.reset_cache()
        with compile_cache.uncached():
            jax.jit(lambda x: x * 3 + 26).lower(jnp.ones(3)).compile()
            assert entries() == []                          # not written
        jax.jit(lambda x: x * 3 + 26).lower(jnp.ones(3)).compile()
        written = entries()
        assert len(written) == 1                            # in force again
        with compile_cache.uncached():
            os.remove(tmp_path / written[0])    # a read would now fail
            with open(tmp_path / written[0], "wb") as f:
                f.write(b"not an executable")
            jax.jit(lambda x: x * 3 + 26).lower(jnp.ones(3)).compile()
        assert jax.config.jax_enable_compilation_cache
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()
