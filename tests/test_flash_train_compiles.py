"""Compile-only check of the LM train step at the benchmark cell's real size
(pythia-410m widths, 4 rows of 2048 tokens, bfloat16, full scan unroll,
selective MLP recompute) for one chip of a described ``v5e:2x2``: the three
flash kernels stand in the compiled step once a layer each, no row statistic
travels as 128 lane copies, and the step needs no more memory than it did
before the kernels wrote their results in their final form; and for one chip
and for all four, that no buffer of every gradient element (405 062 656 of
them) stands in the step: the sync hands each leaf to ``psum`` as it is, and
on one chip there is no sync. Nothing executes. Bytes are printed
(``pytest -s``) for PERF.md."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.pallas import flash_attention as fa

LAYERS, ROWS, SEQ, HEADS = 24, 4, 2048, 16
# arguments and temporaries of this step at PR 35 (PERF.md §4), GB a chip,
# by the number of chips
PARENT_ARGUMENTS, PARENT_TEMPORARIES = 3.241, {1: 7.866, 4: 8.050}
# every gradient element: what one packed float32 buffer of them would hold
GRADIENT_ELEMENTS = 405062656


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


def compile_step(topo, n_chips):
    import optax
    from horovod_tpu.parallel import trainer

    cfg = tfm.TransformerConfig(
        vocab_size=50304, d_model=1024, n_heads=HEADS, head_dim=64,
        n_layers=LAYERS, d_ff=4096, max_seq=SEQ, dtype=jnp.bfloat16,
        dp_axis="hvd", scan_unroll=LAYERS, remat=False, mlp_recompute=True)
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("hvd",))
    repl = NamedSharding(mesh, P())
    shaped = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl), tree)
    opt = optax.sgd(0.01, momentum=0.9)
    _, step = trainer.make_transformer_train_step(cfg, opt, mesh)
    params = jax.eval_shape(lambda: tfm.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    assert sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(params)) == GRADIENT_ELEMENTS
    state = trainer.TrainState(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl), shaped(params),
        shaped(jax.eval_shape(opt.init, params)))
    tokens = jax.ShapeDtypeStruct((ROWS * n_chips, SEQ), jnp.int32,
                                  sharding=NamedSharding(mesh, P("hvd")))
    return step.lower(state, tokens, tokens).compile()


def check_memory(compiled, n_chips):
    m = compiled.memory_analysis()
    arguments = m.argument_size_in_bytes / 1e9
    temporaries = m.temp_size_in_bytes / 1e9
    print(f"\npythia410m train step, {n_chips} chip(s): arguments "
          f"{arguments:.3f} GB, temporaries {temporaries:.3f} GB a chip "
          f"(PR 35: {PARENT_ARGUMENTS} + {PARENT_TEMPORARIES[n_chips]})")
    assert arguments <= PARENT_ARGUMENTS + 0.0005
    assert temporaries <= PARENT_TEMPORARIES[n_chips] + 0.0005


def all_reduces(text):
    from horovod_tpu.analysis.rules_ir import hlo_collectives
    return [c for c in hlo_collectives(text) if c["kind"] == "all-reduce"]


def test_pythia_train_step_holds_the_flash_kernels(topo, compiled_kernels):
    compiled = compile_step(topo, 1)
    text = compiled.as_text()

    assert fa.compiled_kernels(text) == {
        "hvd_flash_fwd": LAYERS, "hvd_flash_bwd_dq": LAYERS,
        "hvd_flash_bwd_dkv": LAYERS}
    # m, l, lse and dD went between HBM and the kernels as [B*H, S, 128]
    assert f"f32[{ROWS * HEADS},{SEQ},128]" not in text
    # one chip exchanges nothing, so it packs nothing and syncs nothing
    assert f"[{GRADIENT_ELEMENTS}]" not in text
    assert not all_reduces(text)
    assert "hvd_grad_sync" not in text
    check_memory(compiled, 1)


def test_pythia_dp4_step_exchanges_the_leaves_as_they_are(topo,
                                                          compiled_kernels):
    compiled = compile_step(topo, 4)
    text = compiled.as_text()

    assert f"[{GRADIENT_ELEMENTS}]" not in text
    reduces = all_reduces(text)
    print(f"\n{len(reduces)} all-reduces: "
          + ", ".join(f"{c['bytes'] / 1e9:.4f}" for c in reduces) + " GB")
    # every gradient byte once, in float32, and the loss's few scalars
    # (the combiner may put one of them into a tuple with gradient leaves)
    assert 0 <= sum(c["bytes"] for c in reduces) - 4 * GRADIENT_ELEMENTS <= 64
    assert any("hvd_grad_sync" in c["op_name"] for c in reduces)
    check_memory(compiled, 4)
