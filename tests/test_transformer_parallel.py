"""Parity tests for the flagship TransformerLM across parallelism axes.

Strategy (the decisive check for manual-SPMD correctness): run the identical
params + batch through (a) the unsharded single-device path and (b) each
sharded mesh composition (DP / TP / SP-ring / SP-ulysses / EP / PP and
combinations) on the 8-device CPU mesh, and require loss and synced gradients
to match to fp32 tolerance. This mirrors the reference's test_torch.py
pattern of asserting collective results against locally computed expectations
(SURVEY §4 tier 1), but end-to-end through a real model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.eager import shard_map
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import trainer as trainer_lib

BASE = dict(vocab_size=64, d_model=32, n_heads=4, head_dim=8, n_layers=2,
            d_ff=64, max_seq=32, dtype=jnp.float32, remat=False)
B, S = 8, 16


def make_batch(seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, BASE["vocab_size"], (B, S)).astype(np.int32)
    labels = rng.randint(0, BASE["vocab_size"], (B, S)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(labels)


def reference_loss_and_grads(cfg_kwargs):
    cfg = tfm.TransformerConfig(dp_axis=None, **cfg_kwargs)
    # jitted: one compile each instead of op-by-op eager dispatch
    params = jax.jit(lambda r: tfm.init_params(cfg, r))(jax.random.PRNGKey(7))
    tokens, labels = make_batch()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(cfg, p, tokens, labels)))(params)
    return params, loss, grads


def sharded_loss_and_grads(cfg, mesh):
    params = jax.jit(lambda r: tfm.init_params(cfg, r))(jax.random.PRNGKey(7))
    tokens, labels = make_batch()
    pspecs = tfm.param_specs(cfg)
    bspec = tfm.batch_spec(cfg)
    sync = tfm.grad_sync_axes(cfg)
    world = int(np.prod([mesh.shape[a] for a in tfm.mesh_axes(cfg)]))

    def f(p, t, l):
        loss, grads = jax.value_and_grad(
            lambda q: tfm.loss_fn(cfg, q, t, l))(p)
        return loss, trainer_lib.sync_gradients(grads, sync, world)

    fn = jax.jit(shard_map(f, mesh, in_specs=(pspecs, bspec, bspec),
                           out_specs=(P(), pspecs)))
    loss, grads = fn(params, tokens, labels)
    return loss, grads


def assert_grads_close(ref, got, atol=2e-4, rtol=2e-3):
    flat_ref = jax.tree.leaves_with_path(ref)
    flat_got = jax.tree.leaves(got)
    for (path, r), g in zip(flat_ref, flat_got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=atol, rtol=rtol,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}")


def mesh_for(shape, names):
    devs = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def test_single_device_loss_finite():
    cfg = tfm.TransformerConfig(dp_axis=None, **BASE)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    tokens, labels = make_batch()
    loss = tfm.loss_fn(cfg, params, tokens, labels)
    assert np.isfinite(float(loss))
    # untrained model ~ uniform: loss near log(V)
    assert abs(float(loss) - np.log(BASE["vocab_size"])) < 1.0


def test_dp_matches_reference():
    _, ref_loss, ref_grads = reference_loss_and_grads(dict(BASE))
    cfg = tfm.TransformerConfig(dp_axis="dp", **BASE)
    loss, grads = sharded_loss_and_grads(cfg, mesh_for((8,), ("dp",)))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert_grads_close(ref_grads, grads)


def test_tp_matches_reference():
    _, ref_loss, ref_grads = reference_loss_and_grads(dict(BASE))
    cfg = tfm.TransformerConfig(dp_axis="dp", tp_axis="tp", **BASE)
    loss, grads = sharded_loss_and_grads(cfg, mesh_for((2, 4), ("dp", "tp")))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert_grads_close(ref_grads, grads)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sp_matches_reference(impl):
    _, ref_loss, ref_grads = reference_loss_and_grads(dict(BASE))
    cfg = tfm.TransformerConfig(dp_axis="dp", sp_axis="sp", attention=impl,
                                **BASE)
    loss, grads = sharded_loss_and_grads(cfg, mesh_for((2, 4), ("dp", "sp")))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert_grads_close(ref_grads, grads)


def test_ep_matches_reference():
    # capacity_factor=E so nothing drops; aux weight 0 because the
    # load-balance loss is legitimately computed over per-chip token groups
    # when sharded (nonlinear in the mean, so it cannot match the global
    # computation exactly).
    kw = dict(BASE, num_experts=4, capacity_factor=float(4),
              moe_aux_weight=0.0)
    _, ref_loss, ref_grads = reference_loss_and_grads(dict(kw, ep_axis=None))
    cfg = tfm.TransformerConfig(dp_axis="dp", ep_axis="ep", **kw)
    loss, grads = sharded_loss_and_grads(cfg, mesh_for((2, 4), ("dp", "ep")))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)
    assert_grads_close(ref_grads, grads, atol=5e-4)


def test_pp_matches_reference():
    kw = dict(BASE, n_layers=4)
    _, ref_loss, ref_grads = reference_loss_and_grads(dict(kw))
    cfg = tfm.TransformerConfig(dp_axis="dp", pp_axis="pp",
                                n_microbatches=2, **kw)
    loss, grads = sharded_loss_and_grads(cfg, mesh_for((2, 4), ("dp", "pp")))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert_grads_close(ref_grads, grads)


def test_dp_tp_sp_combined():
    _, ref_loss, ref_grads = reference_loss_and_grads(dict(BASE))
    cfg = tfm.TransformerConfig(dp_axis="dp", tp_axis="tp", sp_axis="sp",
                                **BASE)
    loss, grads = sharded_loss_and_grads(
        cfg, mesh_for((2, 2, 2), ("dp", "tp", "sp")))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert_grads_close(ref_grads, grads)


def test_full_train_step_loss_decreases():
    cfg = tfm.TransformerConfig(dp_axis="dp", tp_axis="tp", **BASE)
    mesh = mesh_for((2, 4), ("dp", "tp"))
    init_fn, step = trainer_lib.make_transformer_train_step(
        cfg, optax.adam(1e-2), mesh)
    state = init_fn(jax.random.PRNGKey(0))
    tokens, labels = make_batch()
    losses = []
    for _ in range(8):
        state, loss = step(state, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses
    assert int(state.step) == 8


def test_scan_unroll_equivalence():
    """Layer-stack unroll (full = the v5e perf default, PERF.md r5; 2 =
    the non-dividing remainder path over 3 layers) is numerically
    identical to the compact scan."""
    kw = dict(vocab_size=128, d_model=64, n_heads=4, head_dim=16,
              n_layers=3, d_ff=128, max_seq=32, dtype=jnp.float32,
              dp_axis=None, remat=False)
    tokens = np.random.RandomState(0).randint(0, 128, (2, 16))
    params = tfm.init_params(tfm.TransformerConfig(**kw),
                             jax.random.PRNGKey(0))
    losses = []
    for unroll in (1, 2, 3):
        cfg = tfm.TransformerConfig(scan_unroll=unroll, **kw)
        losses.append(float(jax.jit(
            lambda p, t: tfm.loss_fn(cfg, p, t, t))(params, tokens)))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    np.testing.assert_allclose(losses[0], losses[2], rtol=1e-6)


# --- sync_gradients: one psum a leaf, no buffer around the exchange ---------

def packed_sync(grads, sync_axes, world):
    """What ``sync_gradients`` was until PR 38, kept as the reference: the
    leaves of one axes tuple raveled into one buffer, one psum an axis on
    it, the scale, and every leaf cut out and reshaped back."""
    from horovod_tpu.ops.fusion import group_leaves_by_axes
    treedef, leaves, groups = group_leaves_by_axes(grads, sync_axes)
    for axes, idxs in groups.items():
        buf = jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        for ax in axes:
            buf = jax.lax.psum(buf, ax)
        buf = buf * jnp.float32(1.0 / world) if world != 1 else buf
        offsets = np.cumsum([0] + [leaves[i].size for i in idxs])
        for i, o in zip(idxs, offsets):
            leaves[i] = buf[o:o + leaves[i].size].reshape(leaves[i].shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# mesh shape, axis names, and a coarse sync_axes tree over the gradient
# tree below: a tuple at an interior node covers its whole subtree
SYNC_CASES = {
    "dp2": ((2,), ("dp",),
            {"embed": ("dp",), "layers": ("dp",), "head": ("dp",)}),
    "dp2_tp2_mixed_axes": (
        (2, 2), ("dp", "tp"),
        {"embed": ("dp", "tp"), "layers": {"wq": ("dp",), "norm": ("dp", "tp"),
                                           "bias": ()},
         "head": ("dp",)}),
    "world1": ((1,), ("dp",),
               {"embed": ("dp",), "layers": ("dp",), "head": ("dp",)}),
}


def _sync_case(name):
    shape, names, sync = SYNC_CASES[name]
    mesh = mesh_for(shape, names)
    world = int(np.prod(shape))
    rng = np.random.RandomState(3)
    # a leading axis of one row a chip, so every chip holds other numbers
    grads = {"embed": rng.randn(world, 7, 5), "head": rng.randn(world, 5, 3),
             "layers": {"wq": rng.randn(world, 2, 5, 5),
                        "norm": rng.randn(world, 2, 5),
                        "bias": rng.randn(world, 3)}}
    grads = jax.tree.map(lambda g: jnp.asarray(g, jnp.float32), grads)
    return mesh, names, sync, world, grads


@pytest.mark.parametrize("case", sorted(SYNC_CASES))
def test_sync_gradients_gives_what_the_packed_form_gave(case):
    mesh, names, sync, world, grads = _sync_case(case)
    spec = jax.tree.map(lambda _: P(names), grads)

    def run(fn):
        return jax.jit(shard_map(
            lambda g: fn(g, sync, world), mesh, in_specs=(spec,),
            out_specs=spec))(grads)

    got, want = run(trainer_lib.sync_gradients), run(packed_sync)
    assert jax.tree.structure(got) == jax.tree.structure(grads)
    for (path, w), g in zip(jax.tree.leaves_with_path(want),
                            jax.tree.leaves(got)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"leaf {jax.tree_util.keystr(path)}")
    if world == 1:      # nothing to exchange: the very arrays come back
        assert all(a is b for a, b in zip(
            jax.tree.leaves(trainer_lib.sync_gradients(grads, sync, 1)),
            jax.tree.leaves(grads)))


def _dp_step_text(n_chips):
    cfg = tfm.TransformerConfig(dp_axis="dp", **BASE)
    opt = optax.sgd(0.01, momentum=0.9)
    _, step = trainer_lib.make_transformer_train_step(
        cfg, opt, mesh_for((n_chips,), ("dp",)))
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    state = trainer_lib.TrainState(
        jax.ShapeDtypeStruct((), jnp.int32), params,
        jax.eval_shape(opt.init, params))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    text = step.lower(state, tokens, tokens).compile().as_text()
    n_elements = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    return text, n_elements


@pytest.mark.parametrize("n_chips", [2, 1])
def test_dp_step_packs_no_gradient_buffer(n_chips):
    """No array of every gradient element stands anywhere in the compiled
    step, and on one chip nothing at all is traced under the sync's scope
    (PR 38: it packed and unpacked a buffer nobody exchanged)."""
    text, n_elements = _dp_step_text(n_chips)
    assert f"f32[{n_elements}]" not in text
    under_scope = [l for l in text.splitlines() if "hvd_grad_sync" in l]
    if n_chips == 1:
        assert not under_scope, under_scope[:3]
    else:
        assert any("all-reduce" in l for l in under_scope)
