"""A whole run with the timed path broken underneath comes out as not
correct: once for each fault a cell can have. The harness's look for a chip
is skipped and nothing else; the faults are planted in the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy


@pytest.fixture(autouse=True)
def interpret_kernels(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")


def over(line):
    return {k for k, (value, limit) in line["compared"].items()
            if not value <= limit}


@pytest.mark.parametrize("name", ["lm_train_1", "cnn_train_1"])
def test_step_that_returns_its_state_unchanged(monkeypatch, name):
    import importlib
    family = importlib.import_module(
        "benchmarks.families." + bench_toy.cell(name).config["family"])

    def step(self, k):
        # the state is donated: give the program a copy and keep the old one
        state = jax.tree.map(jnp.copy, self.state)
        _, loss = self.compiled(state, *self._batches[k % self.pool])
        return loss

    monkeypatch.setattr(family.TrainProgram, "step", step)
    line = bench_toy.rehearse(name)
    assert line["correct"] is False
    # nothing moved: the change reads 1 by the comparison's measure
    changed = "delta" if "delta" in line["compared"] else "delta_med"
    assert changed in over(line)
    assert line["compared"][changed][0] == pytest.approx(1.0, abs=1e-6)


def test_half_of_the_batch_left_out_lm(monkeypatch):
    from horovod_tpu.models import transformer as tfm
    whole = tfm.loss_fn

    def half(cfg, params, tokens, labels):
        n = tokens.shape[0] // 2
        return whole(cfg, params, tokens[:n], labels[:n])

    monkeypatch.setattr(tfm, "loss_fn", half)
    line = bench_toy.rehearse("lm_train_1")
    assert line["correct"] is False
    assert "grad1" in over(line)


def test_half_of_the_batch_left_out_cnn(monkeypatch):
    import optax
    whole = optax.softmax_cross_entropy_with_integer_labels

    def half(logits, labels, **kw):
        n = logits.shape[0] // 2
        loss = whole(logits[:n], labels[:n], **kw)
        return jnp.concatenate([loss, loss])    # the mean is over the half

    monkeypatch.setattr(optax, "softmax_cross_entropy_with_integer_labels",
                        half)
    line = bench_toy.rehearse("cnn_train_1")
    assert line["correct"] is False
    assert {"grad1_med", "grad1_kernel"} <= over(line)


def test_exchange_between_chips_left_out(monkeypatch):
    from horovod_tpu.parallel import trainer
    monkeypatch.setattr(trainer, "sync_gradients",
                        lambda grads, sync_axes, world: grads)
    line = bench_toy.rehearse("lm_train_4")
    assert line["correct"] is False
    assert "grad1" in over(line)


def test_token_altered_where_it_is_produced(monkeypatch):
    from horovod_tpu.serving import ServeEngine
    sound = ServeEngine.decode_step
    calls = {"n": 0}

    def altered(self, tokens, active=None):
        out = np.array(sound(self, tokens, active=active))
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = (out + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(ServeEngine, "decode_step", altered)
    line = bench_toy.rehearse("serve_closed")
    assert line["correct"] is False
    assert over(line) == {"served_logit_gap"}


def test_request_cut_short_counts_as_failed(monkeypatch):
    from horovod_tpu.serving import ServeScheduler
    sound = ServeScheduler.submit

    def short(self, req):
        sound(self, req)
        if req.rid % 5 == 0:
            req.max_new_tokens -= 1             # one token never comes
    monkeypatch.setattr(ServeScheduler, "submit", short)
    line = bench_toy.rehearse("serve_closed")
    # the served tokens are sound, so the logits agree; the run is still not
    # correct because requests failed
    assert line["failed"] > 0
