"""Fused Pallas conv+BN (ops/pallas/conv_bn.py, models/fused_block.py).

Equivalence contract: the fused bottleneck path must match the unfused
nn.Conv + nn.BatchNorm composition — outputs, gradients, and running
statistics — parameter-for-parameter (trees mapped by name). Kernels run
interpreted on CPU here; the real-chip A/B lives in bench.py/PERF.md.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import flax.linen as nn
from flax.core import freeze, unfreeze
from flax.traverse_util import flatten_dict, unflatten_dict

from horovod_tpu.ops.pallas.conv_bn import conv1x1_bn_stats

jax.config.update("jax_enable_x64", False)


def _ref(x, w, inv=None, shift=None, strides=(1, 1)):
    if strides != (1, 1):
        x = x[:, ::strides[0], ::strides[1], :]
    if inv is not None:
        x = jnp.maximum(x * inv + shift, 0.0)
    y = jnp.einsum("nhwk,kc->nhwc", x, w)
    s1 = jnp.sum(y.astype(jnp.float32), axis=(0, 1, 2))
    s2 = jnp.sum(jnp.square(y.astype(jnp.float32)), axis=(0, 1, 2))
    return y, s1, s2


CASES = [
    (2, 8, 8, 16, 32, (1, 1), False),
    (2, 8, 8, 16, 32, (1, 1), True),      # prologue
    (3, 7, 7, 130, 70, (1, 1), True),     # M, K, N all need padding
    (2, 8, 8, 16, 32, (2, 2), True),      # strided (projection conv)
]


@pytest.mark.parametrize("n,h,w,k,c,stride,prologue", CASES)
def test_kernel_forward_matches_composition(n, h, w, k, c, stride, prologue):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, h, w, k), jnp.float32)
    wt = jnp.asarray(rng.randn(k, c) * 0.1, jnp.float32)
    inv = jnp.asarray(rng.rand(k) + 0.5, jnp.float32) if prologue else None
    shift = jnp.asarray(rng.randn(k) * 0.1, jnp.float32) if prologue else None
    y, s1, s2 = conv1x1_bn_stats(x, wt, inv, shift, strides=stride,
                                 interpret=True)
    yr, s1r, s2r = _ref(x, wt, inv, shift, strides=stride)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s1r),
                               rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s2r),
                               rtol=2e-4, atol=2e-2)


def test_kernel_gradients_match_composition():
    rng = np.random.RandomState(1)
    n, h, w, k, c = 2, 4, 4, 16, 32
    x = jnp.asarray(rng.randn(n, h, w, k), jnp.float32)
    wt = jnp.asarray(rng.randn(k, c) * 0.1, jnp.float32)
    inv = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
    shift = jnp.asarray(rng.randn(k) * 0.1, jnp.float32)
    c1 = jnp.asarray(rng.randn(c), jnp.float32)
    c2 = jnp.asarray(rng.randn(c) * 0.01, jnp.float32)

    def loss(fn):
        def go(x, wt, inv, shift):
            y, s1, s2 = fn(x, wt, inv, shift)
            return (jnp.sum(y * y) * 0.5 + jnp.sum(s1 * c1)
                    + jnp.sum(s2 * c2))
        return go

    gp = jax.grad(loss(lambda *a: conv1x1_bn_stats(*a, interpret=True)),
                  argnums=(0, 1, 2, 3))(x, wt, inv, shift)
    gr = jax.grad(loss(_ref), argnums=(0, 1, 2, 3))(x, wt, inv, shift)
    for a, b, nm in zip(gp, gr, "x w inv shift".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-3, err_msg=nm)


# ---------------------------------------------------------------------------
# Full-model equivalence: fused ResNet vs plain ResNet, mapped params.
# ---------------------------------------------------------------------------

from horovod_tpu.models.fused_block import (  # noqa: E402
    fused_to_plain_variables, plain_to_fused_variables,
    translate_fused_key as _translate_key)

_map_tree = plain_to_fused_variables  # checkpoint converter IS the mapping


def _models():
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet
    kw = dict(stage_sizes=[1, 1], block_cls=BottleneckBlock,
              num_classes=10, num_filters=8, dtype=jnp.float32)
    plain = ResNet(**kw)
    fused = ResNet(fused_conv_bn=True, interpret=True, **kw)
    return plain, fused


def test_fused_resnet_matches_plain_train_mode():
    plain, fused = _models()
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                    jnp.float32)
    # jitted throughout: one compile per call instead of op-by-op eager
    # dispatch (each interpreted pallas_call is a program of its own)
    def init(m):
        return jax.jit(lambda r, a: m.init(r, a, train=True))(
            jax.random.PRNGKey(0), x)

    def apply(m, v):
        return jax.jit(lambda v, a: m.apply(
            v, a, train=True, mutable=["batch_stats"]))(v, x)

    vp = init(plain)
    vf = _map_tree(init(fused), vp)

    op, msp = apply(plain, vp)
    of, msf = apply(fused, vf)
    np.testing.assert_allclose(np.asarray(of), np.asarray(op),
                               rtol=5e-4, atol=5e-4)
    # running statistics advanced identically
    fp = flatten_dict(unfreeze(msp["batch_stats"]))
    ff = flatten_dict(unfreeze(msf["batch_stats"]))
    for k, v in ff.items():
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(fp[_translate_key(k)]),
            rtol=1e-3, atol=1e-4, err_msg=str(k))

    # gradients match through the custom VJP, parameter-for-parameter
    y = jnp.asarray(np.random.RandomState(1).randint(0, 10, (2,)))

    def loss(model, variables):
        def go(params):
            logits, _ = model.apply(
                {**variables, "params": params}, x, train=True,
                mutable=["batch_stats"])
            return jnp.mean(
                -jax.nn.log_softmax(logits)[jnp.arange(2), y])
        return go

    gp = jax.jit(jax.grad(loss(plain, vp)))(vp["params"])
    gf = jax.jit(jax.grad(loss(fused, vf)))(vf["params"])
    fgp = flatten_dict(unfreeze(gp))
    fgf = flatten_dict(unfreeze(gf))
    for k, v in fgf.items():
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(fgp[_translate_key(k)]),
            rtol=5e-3, atol=5e-4, err_msg=str(k))


def test_fused_resnet_matches_plain_eval_mode():
    plain, fused = _models()
    x = jnp.asarray(np.random.RandomState(2).randn(2, 32, 32, 3),
                    jnp.float32)
    def init(m):
        return jax.jit(lambda r, a: m.init(r, a, train=True))(
            jax.random.PRNGKey(0), x)

    vp = init(plain)
    vf = _map_tree(init(fused), vp)
    op = jax.jit(lambda v, a: plain.apply(v, a, train=False))(vp, x)
    of = jax.jit(lambda v, a: fused.apply(v, a, train=False))(vf, x)
    np.testing.assert_allclose(np.asarray(of), np.asarray(op),
                               rtol=5e-4, atol=5e-4)


def test_non_dividing_cout_covers_all_columns():
    # cout=576 -> np_=640: bn must divide 640 or trailing columns would be
    # silently uninitialized (review regression).
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 8, 8, 32), jnp.float32)
    wt = jnp.asarray(rng.randn(32, 576) * 0.1, jnp.float32)
    y, s1, s2 = conv1x1_bn_stats(x, wt, interpret=True)
    yr, s1r, s2r = _ref(x, wt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s1r),
                               rtol=2e-4, atol=2e-3)


def test_non_power_of_two_block_m_rejected():
    x = jnp.zeros((1, 8, 8, 32), jnp.float32)
    wt = jnp.zeros((32, 64), jnp.float32)
    with pytest.raises(ValueError, match="power of two"):
        conv1x1_bn_stats(x, wt, block_m=384, interpret=True)


def test_non_relu_act_rejected():
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet
    model = ResNet(stage_sizes=[1], block_cls=BottleneckBlock,
                   num_classes=4, num_filters=8, dtype=jnp.float32,
                   act=nn.swish, fused_conv_bn=True, interpret=True)
    with pytest.raises(ValueError, match="relu"):
        model.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 32, 32, 3), jnp.float32), train=True)


def test_checkpoint_conversion_round_trips():
    """plain -> fused -> plain must reproduce the plain checkpoint
    exactly (the public converter pair documents/fixes the layout break
    the fused_conv_bn flag introduces)."""
    plain, fused = _models()
    x = jnp.zeros((1, 16, 16, 3), jnp.float32)
    pv = jax.jit(plain.init)(jax.random.PRNGKey(0), x)
    fv_tmpl = jax.jit(fused.init)(jax.random.PRNGKey(1), x)
    fv = plain_to_fused_variables(fv_tmpl, pv)
    back = fused_to_plain_variables(pv, fv)
    for (ka, a), (kb, b) in zip(
            sorted(flatten_dict(unfreeze(pv)).items()),
            sorted(flatten_dict(unfreeze(back)).items())):
        assert ka == kb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
