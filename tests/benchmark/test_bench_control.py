"""The control: the plain reference put in the program's place and computed
in fp8, the nearest precision below the bfloat16 the configurations state,
has to come out as not correct, at a size a test can hold. (On the chip it
was read at the cells' own sizes; PERF.md has those readings.)"""

import importlib

import jax
import numpy as np
import pytest

import bench_toy
from benchmarks.lib import compare, lowprec


@pytest.fixture(autouse=True)
def interpret_kernels(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")


@pytest.mark.parametrize("name", ["lm_train_1", "lm_train_4", "cnn_train_1"])
def test_fp8_reference_fails_a_training_cell(name):
    cell = bench_toy.cell(name)
    family = importlib.import_module(
        "benchmarks.families." + cell.config["family"])
    steps = cell.traffic["check_steps"]
    prog = family.TrainProgram(cell.config, cell.traffic, 3,
                               jax.devices()[:cell.chips])
    prog.release()
    want = prog.reference(lowprec.F32, steps)
    control = prog.reference(lowprec.FP8, steps)
    ok, table = compare.judge(compare.training_numbers(control, want),
                              cell.traffic["limits"])
    assert ok is False, table
    # and the reference against itself is exact
    again = prog.reference(lowprec.F32, steps)
    ok, table = compare.judge(compare.training_numbers(again, want),
                              cell.traffic["limits"])
    assert ok is True and max(v for v, _ in table.values()) < 1e-6


def test_fp8_reference_fails_the_serve_cell():
    """At each position of the same prompts and tokens, the gap of the token
    that fp8 puts first; it need not decode."""
    from benchmarks.families import transformer_lm as lm
    cell = bench_toy.cell("serve_closed")
    rng = np.random.default_rng(0)
    vocab = cell.config["vocab_size"]
    served = [(rng.integers(0, vocab, n), list(rng.integers(0, vocab, 8)))
              for n in (40, 24, 33, 17, 48, 29)]
    args = (cell.config, 5, jax.devices()[0], lowprec.F32, served, 64)
    # the reference judged against its own first choice is exact
    own = lm.served_token_gaps(*args, against=lowprec.F32)
    assert max(g.max() for g in own) == 0.0
    control = lm.served_token_gaps(*args, against=lowprec.FP8)
    worst = float(max(g.max() for g in control))
    ok, _ = compare.judge({"served_logit_gap": worst}, cell.traffic["limits"])
    assert ok is False, worst


def test_e4m3_rounding_is_the_cast():
    import jax.numpy as jnp
    for scale in (1e-4, 1.0, 300.0):
        x = jax.random.normal(jax.random.PRNGKey(0), (50_000,)) * scale
        x = x.at[:3].set(jnp.array([0.0, scale * 1e-5, -scale * 6.0]))
        amax = jnp.max(jnp.abs(x))
        cast = ((x / (amax / 448.0)).astype(jnp.float8_e4m3fn)
                .astype(jnp.float32) * (amax / 448.0))
        assert bool(jnp.all(lowprec.round_e4m3(x) == cast))
    assert bool(jnp.all(lowprec.round_e4m3(jnp.zeros((4,))) == 0.0))
