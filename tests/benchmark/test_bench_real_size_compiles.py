"""Compile-only checks: the cells' programs, and the plain references that
follow them, fit a TPU v5e at the real sizes. The TPU compiler runs here
against a described ``v5e:2x2``; nothing executes. ``memory_analysis()`` bytes
are printed (``pytest -s``) for PERF.md. All in this one file: only one
process may hold the TPU library."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from benchmarks.lib import cell as cells, lowprec

HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The program asks the default backend (the CPU here) whether to use its
    kernels; for a described TPU the answer is yes."""
    from horovod_tpu.ops.pallas import flash_attention
    monkeypatch.setattr(flash_attention, "enabled", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _report(name, compiled):
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"\n{name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.3f} GB "
          f"(aliased {m.alias_size_in_bytes / 1e9:.3f} GB)")
    assert total < HBM, f"{name} needs {total / 1e9:.2f} GB"
    return m


def _lm_cell(chips):
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "pythia410m.json"))
    traffic = cells.load_json(os.path.join(
        cells.BENCH_DIR, "traffic", "lm_train_s2048.json"))
    return config, traffic


@pytest.mark.parametrize("chips", [1, 4])
def test_lm_train_step_fits(topo, compiled_kernels, chips):
    import optax
    from horovod_tpu.ops.pallas.flash_attention import compiled_kernels as ck
    from horovod_tpu.parallel import trainer

    from benchmarks.families import transformer_lm as lm
    config, traffic = _lm_cell(chips)
    mesh = Mesh(np.array(topo.devices[:chips]), ("hvd",))
    repl = NamedSharding(mesh, P())
    cfg = lm.program_config(
        config, dp_axis="hvd", scan_unroll=config["num_hidden_layers"],
        remat=False, mlp_recompute=True)
    opt = optax.sgd(0.01, momentum=0.9)
    _, step = trainer.make_transformer_train_step(cfg, opt, mesh)
    params = jax.eval_shape(functools.partial(lm.weights, config),
                            jax.random.PRNGKey(0))
    state = trainer.TrainState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        _shaped(params, repl), _shaped(jax.eval_shape(opt.init, params), repl))
    rows = traffic["rows_per_chip"] * chips
    tokens = jax.ShapeDtypeStruct((rows, traffic["seq_len"]), jnp.int32,
                                  sharding=NamedSharding(mesh, P("hvd")))
    compiled = step.lower(state, tokens, tokens).compile()
    _report(f"pythia410m train step, {chips} chip(s)", compiled)
    kernels = ck(compiled.as_text())
    assert {"hvd_flash_fwd", "hvd_flash_bwd_dq",
            "hvd_flash_bwd_dkv"} <= set(kernels), kernels


@pytest.mark.parametrize("ops", [
    "f32", pytest.param("fp8", marks=pytest.mark.slow)])
def test_lm_reference_block_fits(topo, ops):
    """The plain reference's gradient of one block of rows, beside four
    parameter-sized trees (weights, gradient sum, trace, start)."""
    from benchmarks.families import transformer_lm as lm
    from benchmarks.reference import transformer_lm as ref
    config, traffic = _lm_cell(1)
    one = SingleDeviceSharding(topo.devices[0])
    params = _shaped(jax.eval_shape(functools.partial(lm.weights, config),
                                    jax.random.PRNGKey(0)), one)
    rows = jax.ShapeDtypeStruct(
        (traffic["reference_rows_per_block"], traffic["seq_len"]), jnp.int32,
        sharding=one)
    block = functools.partial(ref.loss_sum, lowprec.BY_NAME[ops],
                              lm.head_dim(config))
    fn = jax.jit(jax.value_and_grad(lambda p, t, l: block(p, t, l)[0]))
    m = _report(f"pythia410m reference block ({ops})",
                fn.lower(params, rows, rows).compile())
    weights = sum(int(np.prod(s.shape)) * 4 for s in jax.tree.leaves(params))
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes + 3 * weights
            < HBM)


def _cnn_cell():
    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", "resnet50.json"))
    traffic = cells.load_json(os.path.join(
        cells.BENCH_DIR, "traffic", "cnn_train_b256.json"))
    return config, traffic


def test_resnet_train_step_fits(topo, compiled_kernels):
    import optax

    from horovod_tpu import models
    from horovod_tpu.parallel.trainer import jit_step

    from benchmarks.families import resnet as rn
    config, traffic = _cnn_cell()
    one = SingleDeviceSharding(topo.devices[0])
    model = models.ResNet(
        stage_sizes=config["stage_sizes"],
        block_cls=models.resnet.BottleneckBlock,
        num_classes=config["num_classes"], dtype=jnp.bfloat16,
        **traffic["program"])
    # the sync of DistributedOptimizer needs a live context; the step's
    # compute and memory on one chip are those of the plain optimizer
    opt = optax.sgd(0.01, momentum=0.9)

    @jit_step
    def step(state, x, y):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean(), upd["batch_stats"]

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), stats, opt_state), loss

    variables = jax.eval_shape(functools.partial(rn.weights, config),
                               jax.random.PRNGKey(0))
    params = _shaped(variables["params"], one)
    state = (params, _shaped(variables["batch_stats"], one),
             _shaped(jax.eval_shape(opt.init, variables["params"]), one))
    rows, size = traffic["rows_per_chip"], config["image_size"]
    x = jax.ShapeDtypeStruct((rows, size, size, 3), jnp.bfloat16, sharding=one)
    y = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
    _report("resnet50 train step, 256 images", step.lower(state, x, y).compile())


@pytest.mark.slow      # two minutes each: outside the tier-1 budget
@pytest.mark.parametrize("ops", ["f32", "fp8"])
def test_resnet_reference_fits(topo, ops):
    from benchmarks.families import resnet as rn
    from benchmarks.reference import resnet as ref
    config, traffic = _cnn_cell()
    one = SingleDeviceSharding(topo.devices[0])
    params = _shaped(jax.eval_shape(
        lambda k: rn.weights(config, k)["params"], jax.random.PRNGKey(0)), one)
    rows, size = traffic["rows_per_chip"], config["image_size"]
    x = jax.ShapeDtypeStruct((rows, size, size, 3), jnp.bfloat16, sharding=one)
    y = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
    block = functools.partial(ref.loss_sum, lowprec.BY_NAME[ops],
                              tuple(config["stage_sizes"]), rn.NORM)
    fn = jax.jit(jax.value_and_grad(lambda p, a, b: block(p, a, b)[0]))
    _report(f"resnet50 reference, 256 images ({ops})",
            fn.lower(params, x, y).compile())


@pytest.mark.parametrize("slots", [16, 24])
def test_serve_decode_step_fits(topo, compiled_kernels, slots):
    """The decode program over the paged pool at ``slots`` slots of 2048
    context, beside the weights: what the serve cell holds on its chip."""
    from horovod_tpu.serving import engine as eng, kv_cache as kvc

    from benchmarks.families import transformer_lm as lm
    config, _ = _lm_cell(1)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = lm.program_config(config, dp_axis=None)
    page, ctx = 128, 2048
    pages_per_slot = ctx // page
    pool = kvc.PagePool(cfg.n_layers, slots * pages_per_slot, page,
                        cfg.n_heads, cfg.head_dim, dtype=cfg.dtype)
    k_shape = jax.eval_shape(pool.alloc_arrays)[0]
    kv = jax.ShapeDtypeStruct(k_shape.shape, k_shape.dtype, sharding=one)
    params = _shaped(jax.eval_shape(functools.partial(lm.weights, config),
                                    jax.random.PRNGKey(0)), one)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    fn = jax.jit(functools.partial(eng._decode_body, cfg),
                 donate_argnums=(1, 2))
    compiled = fn.lower(params, kv, kv, i32(slots, pages_per_slot),
                        i32(slots), i32(slots)).compile()
    m = _report(f"pythia410m serve decode, {slots} slots", compiled)
    from horovod_tpu.ops.pallas.flash_attention import compiled_kernels as ck
    assert "hvd_paged_decode" in ck(compiled.as_text())
    print(f"  K+V pool {2 * np.prod(kv.shape) * 2 / 1e9:.3f} GB, weights "
          f"{sum(np.prod(s.shape) * 4 for s in jax.tree.leaves(params)) / 1e9:.3f} GB")
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
