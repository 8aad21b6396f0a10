"""Compile-only check of the LM train step at the benchmark cell's real size
(pythia-410m widths, 4 rows of 2048 tokens, bfloat16, full scan unroll,
selective MLP recompute) for one chip of a described ``v5e:2x2``: the three
flash kernels stand in the compiled step once a layer each, no row statistic
travels as 128 lane copies, and the step needs no more memory than it did
before the kernels wrote their results in their final form. Nothing
executes. Bytes are printed (``pytest -s``) for PERF.md."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.pallas import flash_attention as fa

LAYERS, ROWS, SEQ, HEADS = 24, 4, 2048, 16
# arguments and temporaries of this step at PR 35 (PERF.md §4), GB
PARENT_ARGUMENTS, PARENT_TEMPORARIES = 3.241, 7.866


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
    monkeypatch.setattr(fa, "enabled", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def test_pythia_train_step_holds_the_flash_kernels(topo, compiled_kernels):
    import optax
    from horovod_tpu.parallel import trainer

    cfg = tfm.TransformerConfig(
        vocab_size=50304, d_model=1024, n_heads=HEADS, head_dim=64,
        n_layers=LAYERS, d_ff=4096, max_seq=SEQ, dtype=jnp.bfloat16,
        dp_axis="hvd", scan_unroll=LAYERS, remat=False, mlp_recompute=True)
    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    repl = NamedSharding(mesh, P())
    shaped = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl), tree)
    opt = optax.sgd(0.01, momentum=0.9)
    _, step = trainer.make_transformer_train_step(cfg, opt, mesh)
    params = jax.eval_shape(lambda: tfm.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    state = trainer.TrainState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl), shaped(params),
        shaped(jax.eval_shape(opt.init, params)))
    tokens = jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32,
                                  sharding=NamedSharding(mesh, P("hvd")))
    compiled = step.lower(state, tokens, tokens).compile()
    text = compiled.as_text()

    assert fa.compiled_kernels(text) == {
        "hvd_flash_fwd": LAYERS, "hvd_flash_bwd_dq": LAYERS,
        "hvd_flash_bwd_dkv": LAYERS}
    # m, l, lse and dD went between HBM and the kernels as [B*H, S, 128]
    assert f"f32[{ROWS * HEADS},{SEQ},128]" not in text

    m = compiled.memory_analysis()
    arguments = m.argument_size_in_bytes / 1e9
    temporaries = m.temp_size_in_bytes / 1e9
    print(f"\npythia410m train step, 1 chip: arguments {arguments:.3f} GB, "
          f"temporaries {temporaries:.3f} GB (PR 35: {PARENT_ARGUMENTS} + "
          f"{PARENT_TEMPORARIES})")
    assert arguments <= PARENT_ARGUMENTS + 0.0005
    assert temporaries <= PARENT_TEMPORARIES + 0.0005
