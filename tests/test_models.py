"""Model zoo tests: shapes, dtypes, and the DP trainer on flax models
(the pytorch_mnist.py / pytorch_imagenet_resnet50.py-equivalent workloads,
BASELINE.md configs 1-3)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu as hvd
from horovod_tpu.models import MLP, MnistCNN, ResNet18, ResNet50
from horovod_tpu.parallel import trainer as trainer_lib


def test_mlp_forward():
    m = MLP()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 28, 28)))
    out = m.apply(params, jnp.zeros((4, 28, 28)))
    assert out.shape == (4, 10)


def test_mnist_cnn_forward():
    m = MnistCNN()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 28, 28, 1)))
    out = m.apply(params, jnp.zeros((4, 28, 28, 1)))
    assert out.shape == (4, 10)


def test_resnet50_forward_shapes():
    m = ResNet50(num_classes=10, dtype=jnp.float32)
    # jitted: one compile each instead of an op-by-op eager trace
    vars_ = jax.jit(m.init)(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    out = jax.jit(lambda v, x: m.apply(v, x, train=False))(
        vars_, jnp.zeros((2, 32, 32, 3)))
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    # bottleneck expansion: last stage has 512*4 channels
    leaves = jax.tree.leaves(vars_["params"])
    assert any(l.shape[-1] == 2048 for l in leaves)


def test_resnet18_train_mode_updates_batch_stats():
    m = ResNet18(num_classes=10, dtype=jnp.float32)
    vars_ = jax.jit(m.init)(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    out, new_state = jax.jit(lambda v, x: m.apply(
        v, x, train=True, mutable=["batch_stats"]))(
        vars_, jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 10)
    old = jax.tree.leaves(vars_["batch_stats"])
    new = jax.tree.leaves(new_state["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


@pytest.mark.slow   # ~35-85s of CPU conv compiles; out of the tier-1 budget
def test_sync_batch_norm_resnet(hvd_ctx):
    """bn_cross_replica_axis + bind_axis trainer: cross-replica BN stats
    (ref torch/sync_batch_norm.py parity) must train without unbound-axis
    errors and produce finite decreasing loss."""
    mesh = hvd.mesh()
    model = ResNet18(num_classes=4, dtype=jnp.float32,
                     bn_cross_replica_axis="hvd")
    rng = np.random.RandomState(0)
    x = rng.rand(16, 16, 16, 3).astype(np.float32)
    y = rng.randint(0, 4, (16,))
    vars_ = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)))
    bn_state = vars_["batch_stats"]

    def loss_fn(p, batch):
        logits, _ = model.apply(
            {"params": p, "batch_stats": bn_state}, batch["x"], train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    init_fn, step, put_batch = trainer_lib.data_parallel_train_step(
        loss_fn, optax.adam(1e-3), mesh, axis="hvd", bind_axis=True)
    state = init_fn(vars_["params"])
    batch = put_batch({"x": jnp.asarray(x), "y": jnp.asarray(y)})
    losses = []
    for _ in range(5):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_transformer_max_seq_enforced():
    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, d_model=16, n_heads=2,
                                head_dim=8, n_layers=1, d_ff=16, max_seq=8,
                                dp_axis=None, dtype=jnp.float32, remat=False)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    import pytest
    with pytest.raises(ValueError, match="max_seq"):
        tfm.loss_fn(cfg, params, jnp.zeros((1, 16), jnp.int32),
                    jnp.zeros((1, 16), jnp.int32))


def test_data_parallel_trainer_mnist_mlp(hvd_ctx):
    """MNIST-MLP memorisation with the DP trainer — the pytorch_mnist.py
    parity workload on the 8-chip mesh."""
    mesh = hvd.mesh()
    model = MLP(features=(32,))
    rng = np.random.RandomState(0)
    x = rng.rand(64, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, (64,))

    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))

    def loss_fn(p, batch):
        logits = model.apply(p, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    init_fn, step, put_batch = trainer_lib.data_parallel_train_step(
        loss_fn, optax.adam(1e-2), mesh, axis="hvd")
    state = init_fn(params)
    batch = put_batch({"x": jnp.asarray(x), "y": jnp.asarray(y)})
    losses = []
    for _ in range(20):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses


def test_resnet_space_to_depth_stem(hvd_ctx):
    """s2d stem (TPU MXU optimization) produces the same output shape and
    trains; parity: conv_init 7x7/s2 is expressible as the 4x4/s1 conv on
    the s2d input (MLPerf construction)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import ResNet18

    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    for s2d in (False, True):
        model = ResNet18(num_classes=10, space_to_depth=s2d)
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), x)
        out = jax.jit(model.apply)(variables, x)
        assert out.shape == (2, 10)
        stem = [k for k in variables["params"] if k.startswith("conv_init")]
        assert stem == (["conv_init_s2d"] if s2d else ["conv_init"])
        kernel = variables["params"][stem[0]]["kernel"]
        assert kernel.shape == ((4, 4, 12, 64) if s2d else (7, 7, 3, 64))


def test_space_to_depth_stem_mathematically_equivalent(hvd_ctx):
    """The MLPerf construction: a 7x7/s2 conv equals the 4x4/s1 conv on
    the space-to-depth input with the zero-padded-8x8 rearranged kernel —
    verifies the [(2,1),(2,1)] padding derivation numerically."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn

    rng = np.random.default_rng(0)
    n, hgt, wid, c, out_ch = 2, 32, 32, 3, 8
    x = jnp.asarray(rng.standard_normal((n, hgt, wid, c)), jnp.float32)
    w7 = jnp.asarray(rng.standard_normal((7, 7, c, out_ch)), jnp.float32)

    y_ref = jax.lax.conv_general_dilated(
        x, w7, window_strides=(2, 2), padding=[(3, 3), (3, 3)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))

    # Zero-pad to 8x8 with one leading row/col: W8[u+1, v+1] = W7[u, v].
    w8 = jnp.pad(w7, [(1, 0), (1, 0), (0, 0), (0, 0)])
    # Rearrange to the s2d kernel: W4[s, t, (a, b, ch), o] = W8[2s+a, 2t+b].
    w4 = (w8.reshape(4, 2, 4, 2, c, out_ch)
             .transpose(0, 2, 1, 3, 4, 5)
             .reshape(4, 4, 4 * c, out_ch))
    # Model's s2d input transform (channel order (a, b, ch)).
    x2 = (x.reshape(n, hgt // 2, 2, wid // 2, 2, c)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(n, hgt // 2, wid // 2, 4 * c))
    y_s2d = jax.lax.conv_general_dilated(
        x2, w4, window_strides=(1, 1), padding=[(2, 1), (2, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))

    np.testing.assert_allclose(np.asarray(y_s2d), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_space_to_depth_rejects_odd_dims(hvd_ctx):
    import jax
    import jax.numpy as jnp
    import pytest
    from horovod_tpu.models import ResNet18
    model = ResNet18(num_classes=10, space_to_depth=True)
    with pytest.raises(ValueError, match="even spatial dims"):
        model.init(jax.random.PRNGKey(0), jnp.ones((1, 33, 33, 3)))


def test_folded_bn_matches_flax_batchnorm():
    """FoldedBatchNorm (layout-level BN fix, PERF.md) is numerically
    equivalent to nn.BatchNorm: same normalized output, same running
    stats, train and eval."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.folded_bn import FoldedBatchNorm

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 8, 64), jnp.float32)
    ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                       epsilon=1e-5)
    fold = FoldedBatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5)
    vr = ref.init(jax.random.PRNGKey(1), x)
    vf = fold.init(jax.random.PRNGKey(1), x)
    # same param shapes; copy ref params into folded
    vf = {"params": vr["params"], "batch_stats": vf["batch_stats"]}
    yr, mr = ref.apply(vr, x, mutable=["batch_stats"])
    yf, mf = fold.apply(vf, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(mf["batch_stats"]["mean"]),
        np.asarray(mr["batch_stats"]["mean"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mf["batch_stats"]["var"]),
        np.asarray(mr["batch_stats"]["var"]), rtol=1e-5, atol=1e-6)
    # eval mode (running averages)
    ref_eval = nn.BatchNorm(use_running_average=True, momentum=0.9,
                            epsilon=1e-5)
    fold_eval = FoldedBatchNorm(use_running_average=True, momentum=0.9,
                                epsilon=1e-5)
    ye = ref_eval.apply({"params": vr["params"],
                         "batch_stats": mr["batch_stats"]}, x)
    yef = fold_eval.apply({"params": vr["params"],
                           "batch_stats": mf["batch_stats"]}, x)
    np.testing.assert_allclose(np.asarray(yef), np.asarray(ye),
                               rtol=2e-5, atol=2e-5)


def test_resnet_folded_bn_option():
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import ResNet18

    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    for folded in (False, True):
        model = ResNet18(num_classes=10, dtype=jnp.float32,
                         folded_bn=folded)
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), x)
        logits, _ = jax.jit(lambda v, a: model.apply(
            v, a, train=True, mutable=["batch_stats"]))(variables, x)
        assert logits.shape == (2, 10)
        assert np.isfinite(np.asarray(logits)).all()


def test_vgg16_forward_and_grad():
    """VGG-16 (the reference's 68%@512 bandwidth-worst-case scaling
    workload, docs/benchmarks.rst:13-14): forward shape + a training
    step's gradients are finite; param count matches the published ~138M."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.models.vgg import VGG16

    model = VGG16(num_classes=10, dtype=jnp.float32, classifier_width=64)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                    jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x)
    out = jax.jit(model.apply)(params, x)
    assert out.shape == (2, 10)

    def loss(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, x), jnp.asarray([1, 2])).mean()

    g = jax.jit(jax.grad(loss))(params)
    assert all(np.isfinite(np.asarray(v)).all()
               for v in jax.tree.leaves(g))

    # full-size param count sanity (no init needed: count analytically)
    full = VGG16(num_classes=1000)
    shapes = jax.eval_shape(
        lambda: full.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 224, 224, 3), jnp.bfloat16)))
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(shapes))
    assert 135e6 < n_params < 140e6, n_params


@pytest.mark.slow   # ~35-85s of CPU conv compiles; out of the tier-1 budget
def test_inception_v3_forward_and_grad():
    """Inception V3 (the reference's 90%@512 headline workload,
    docs/benchmarks.rst:13-14): 299-input forward shape, finite training
    gradients, param count in the published ~24-28M band."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.models.inception import InceptionV3

    model = InceptionV3(num_classes=10, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 299, 299, 3),
                    jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    out = model.apply(variables, x, train=False)
    assert out.shape == (1, 10)

    def loss(p):
        logits, _ = model.apply(
            {**variables, "params": p}, x, train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray([3])).mean()

    g = jax.grad(loss)(variables["params"])
    assert all(np.isfinite(np.asarray(v)).all()
               for v in jax.tree.leaves(g))

    full = InceptionV3(num_classes=1000)
    shapes = jax.eval_shape(
        lambda: full.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 299, 299, 3), jnp.bfloat16),
                          train=True))
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(shapes["params"]))
    assert 20e6 < n_params < 28e6, n_params
