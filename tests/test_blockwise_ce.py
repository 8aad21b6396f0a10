"""Blockwise fused cross-entropy (ops/blockwise_ce) + selective MLP
recompute (models/transformer.mlp_recompute).

The contract under test: the chunked-vocab online-logsumexp loss and its
custom-VJP gradients match the naive materialize-the-logits reference
numerically (across chunk sizes, including V not divisible by the chunk),
while never building a [tokens, V]-shaped array in the optimized HLO of
either pass; the TP vocab-parallel CE reuses the same core; and the
selective MLP recompute keeps every d_ff-wide activation out of the saved
residuals.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd  # noqa: F401  (conftest sets up the 8-dev mesh)
from horovod_tpu.config import knobs
from horovod_tpu.ops import blockwise_ce
from horovod_tpu.ops.blockwise_ce import blockwise_cross_entropy

N, D, V = 24, 16, 37          # V deliberately not divisible by the blocks
B, S = 4, 6                   # N = B * S


def _data(dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, S, D), dtype)
    head = jnp.asarray(rng.randn(D, V), dtype)
    labels = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    return x, head, labels


def _naive(x, head, labels):
    """The unfused logsumexp reference (materializes [.., V] logits)."""
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - tgt


@pytest.mark.parametrize("block", [5, 8, 16, 37, 64])
def test_loss_and_grads_match_reference_f32(block):
    x, head, labels = _data()
    got = blockwise_cross_entropy(x, head, labels, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _naive(x, head, labels)), rtol=1e-6, atol=1e-6)

    gb = jax.grad(lambda x, h: jnp.sum(
        blockwise_cross_entropy(x, h, labels, block=block)),
        argnums=(0, 1))(x, head)
    gn = jax.grad(lambda x, h: jnp.sum(_naive(x, h, labels)),
                  argnums=(0, 1))(x, head)
    for b, n in zip(gb, gn):
        np.testing.assert_allclose(np.asarray(b), np.asarray(n),
                                   rtol=1e-5, atol=1e-6)


def test_bf16_matches_reference_within_bf16_tolerance():
    x, head, labels = _data(jnp.bfloat16)
    got = blockwise_cross_entropy(x, head, labels, block=8)
    # Reference in the same compute scheme (f32-accumulated matmul); bf16
    # inputs bound the agreement.
    ref = _naive(x, head, labels)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    gb = jax.grad(lambda x, h: jnp.sum(blockwise_cross_entropy(
        x, h, labels, block=8)), argnums=(0, 1))(x, head)
    gn = jax.grad(lambda x, h: jnp.sum(_naive(x, h, labels)),
                  argnums=(0, 1))(x, head)
    assert gb[0].dtype == jnp.bfloat16 and gb[1].dtype == jnp.bfloat16
    for b, n in zip(gb, gn):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(n, np.float32),
                                   rtol=1e-1, atol=1e-1)


def test_block_larger_than_vocab_and_block_one():
    x, head, labels = _data()
    ref = _naive(x, head, labels)
    for block in (1, V, 10 * V):
        got = blockwise_cross_entropy(x, head, labels, block=block)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def _vocab_shape_re(n_tokens, vocab):
    """Matches any HLO tensor literal whose trailing dims are
    [.., n_tokens, vocab] or [n_tokens, vocab] — the materialized-logits
    shape in any layout the compiler might pick."""
    return re.compile(r"\[(?:\d+,)*%d,%d\]" % (n_tokens, vocab))


def test_no_token_by_vocab_array_in_hlo():
    """The acceptance check: fwd+bwd optimized HLO contains NO
    [tokens, V]-shaped buffer, while the naive path's does."""
    x, head, labels = _data()

    def fused(x, h):
        return jnp.sum(blockwise_cross_entropy(x, h, labels, block=8))

    def naive(x, h):
        return jnp.sum(_naive(x, h, labels))

    pat_flat = _vocab_shape_re(N, V)
    pat_bs = _vocab_shape_re(S, V)     # [B, S, V] spelled with leading dims
    fused_txt = jax.jit(jax.value_and_grad(fused, argnums=(0, 1))) \
        .lower(x, head).compile().as_text()
    naive_txt = jax.jit(jax.value_and_grad(naive, argnums=(0, 1))) \
        .lower(x, head).compile().as_text()
    assert not pat_flat.search(fused_txt) and not pat_bs.search(fused_txt), \
        "blockwise CE materialized a [tokens, V] array"
    assert pat_flat.search(naive_txt) or pat_bs.search(naive_txt), \
        "reference path should materialize logits (test self-check)"


def test_vocab_parallel_ce_reuses_shared_core(hvd_ctx, monkeypatch):
    """The TP path must route through the shared blockwise core, and its
    sharded result must match the naive unfused TP path on global data."""
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.eager import shard_map
    from horovod_tpu.parallel import tensor_parallel as tp_lib

    calls = []
    orig = blockwise_ce.blockwise_cross_entropy

    def spy(*args, **kw):
        calls.append(kw.get("tp_axis"))
        return orig(*args, **kw)

    monkeypatch.setattr(blockwise_ce, "blockwise_cross_entropy", spy)

    rng = np.random.RandomState(3)
    v_tp = 40                          # 5 per shard on the 8-chip mesh
    x = jnp.asarray(rng.randn(B, S, D), jnp.float32)
    head = jnp.asarray(rng.randn(D, v_tp), jnp.float32)
    labels = jnp.asarray(rng.randint(0, v_tp, (B, S)), jnp.int32)
    mesh = hvd.mesh()

    def run(block):
        def per_shard(x, h, l):
            return tp_lib.vocab_parallel_cross_entropy(
                x, h, l, "hvd", block=block)
        fn = jax.jit(shard_map(
            per_shard, mesh=mesh, in_specs=(P(), P(None, "hvd"), P()),
            out_specs=P()))
        return np.asarray(fn(x, head, labels))

    fused = run(block=3)               # does not divide the 5-wide shard
    assert calls and calls[-1] == "hvd", \
        "vocab_parallel_cross_entropy did not call the shared core"
    naive = run(block=0)               # unfused reference path
    np.testing.assert_allclose(fused, naive, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        fused, np.asarray(_naive(x, head, labels)), rtol=1e-5, atol=1e-6)


def test_transformer_loss_fn_blockwise_equals_unfused():
    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(
        vocab_size=101, d_model=32, n_heads=2, head_dim=16, n_layers=2,
        d_ff=128, max_seq=64, dtype=jnp.float32, dp_axis=None, remat=False)
    params = jax.jit(lambda r: tfm.init_params(cfg, r))(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 101, (2, 16)), jnp.int32)
    lab = jnp.asarray(rng.randint(0, 101, (2, 16)), jnp.int32)
    cfg0 = dataclasses.replace(cfg, ce_block_vocab=0, mlp_recompute=False)
    cfgb = dataclasses.replace(cfg, ce_block_vocab=16)
    # jitted: one compile per config instead of op-by-op eager dispatch
    (l0, g0), (lb, gb) = (
        jax.jit(jax.value_and_grad(
            lambda p, c=c: tfm.loss_fn(c, p, tok, lab)))(params)
        for c in (cfg0, cfgb))
    np.testing.assert_allclose(float(l0), float(lb), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_ce_block_knob_is_default(monkeypatch):
    x, head, labels = _data()
    knobs.set_override("HOROVOD_CE_BLOCK_VOCAB", 7)
    try:
        got = blockwise_cross_entropy(x, head, labels)     # block from knob
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_naive(x, head, labels)),
                                   rtol=1e-5, atol=1e-6)
    finally:
        knobs.clear_override("HOROVOD_CE_BLOCK_VOCAB")


# ---------------------------------------------------------------------------
# selective MLP recompute
# ---------------------------------------------------------------------------

def _wide_residuals(cfg, params, tok, lab, d_ff):
    from jax._src.ad_checkpoint import saved_residuals
    from horovod_tpu.models import transformer as tfm
    res = saved_residuals(lambda p: tfm.loss_fn(cfg, p, tok, lab), params)
    return [str(a.shape) for a, note in res
            if "argument" not in note and a.ndim >= 2
            and a.shape[-1] == d_ff]


def test_mlp_recompute_drops_dff_wide_residuals():
    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(
        vocab_size=101, d_model=32, n_heads=2, head_dim=16, n_layers=2,
        d_ff=128, max_seq=64, dtype=jnp.float32, dp_axis=None, remat=False)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 101, (2, 16)), jnp.int32)
    lab = jnp.asarray(rng.randint(0, 101, (2, 16)), jnp.int32)

    saved_off = _wide_residuals(
        dataclasses.replace(cfg, mlp_recompute=False), params, tok, lab, 128)
    saved_on = _wide_residuals(cfg, params, tok, lab, 128)
    assert saved_off, "without recompute the d_ff-wide activations " \
                      "must be saved (test self-check)"
    assert not saved_on, \
        f"mlp_recompute left d_ff-wide residuals saved: {saved_on}"
