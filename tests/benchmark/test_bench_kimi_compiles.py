"""Compile-only checks of the ``kimi_k2_7_code_serve_c32_p12k`` cell's
programs at their real size, for a described TPU v5e (the TPU compiler runs
here; nothing executes): that the decode program and the largest prefill
bucket fit one chip's 16 GiB beside the weights and the latent cache of 32
slots x 12 800 positions, and that bfloat16 weights stay bfloat16 inside
them. ``memory_analysis()`` bytes are printed (``pytest -s``) for PERF.md."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib import cell as cells

HBM = 16 * 2 ** 30
CELL = "kimi_k2_7_code_serve_c32_p12k"


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
def uncached():
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _programs(topo):
    from horovod_tpu.serving import engine as eng, kv_cache as kvc

    from benchmarks.families import kimi_k2 as fam
    cell = cells.load_cell(CELL)
    e = cell.traffic["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    cfg = fam.program_config(cell.config)
    model = eng.serve_model(cfg)
    pages_per_slot = e["max_seq"] // e["page"]
    pool = kvc.PagePool(cfg.n_layers, e["slots"] * pages_per_slot, e["page"],
                        dtype=cfg.dtype, rows=model.cache_rows(cfg))

    def shaped(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = shaped(jax.eval_shape(
        lambda: fam.weights(cell.config, jax.random.PRNGKey(0))))
    held = tuple(jax.ShapeDtypeStruct(s, cfg.dtype, sharding=one)
                 for s in pool.shapes()) + tuple(shaped(model.state(cfg)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    jits = eng.serve_programs(
        cfg, [kvc.pool_format(one, len(s)) for s in pool.shapes()])
    slots = e["slots"]
    return params, pool, {
        "decode": lambda: jits["decode"].lower(
            params, *held, i32(slots, pages_per_slot), i32(slots),
            i32(slots)).compile(),
        "prefill": lambda: jits["prefill"].lower(
            params, *held, i32(pages_per_slot), i32(), i32(),
            i32(e["prefill_chunk"])).compile()}


def _weight_shapes(params):
    """Every weight stack's shape and one layer's slice of it, but the
    router's (served in float32) and the vectors'."""
    out = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if leaf.dtype == jnp.bfloat16 and leaf.ndim >= 2:
            out.add(tuple(leaf.shape))
            if leaf.ndim >= 3:
                out.add(tuple(leaf.shape[1:]))
    return out


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_the_cells_programs_fit_and_widen_no_weight(topo, uncached, name):
    params, pool, programs = _programs(topo)
    compiled = programs[name]()
    m = compiled.memory_analysis()
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(params))
    print(f"\n{CELL} {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} "
          f"GB (weights {weights / 1e9:.3f}, latent pool "
          f"{pool.nbytes() / 1e9:.3f} before the 576 -> 640 lane padding), "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB")
    # ISSUE 39: 3.497 B parameters, 6.99 GB in bfloat16, the router's 11 M
    # in float32
    assert weights == pytest.approx(6.994e9 + 0.022e9, rel=2e-3)
    # 5 blocks x 32 x 12 800 rows of 576 numbers, tiled to 640 lanes:
    # 2.62 GB beside the weights
    assert pool.nbytes() == pytest.approx(5 * 3201 * 128 * 576 * 2)
    assert m.argument_size_in_bytes <= weights + 2.63e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 0.95 * HBM
    # no float32 buffer the size of a weight stack or of a layer of one:
    # every instruction outside the fused computations (inside one, a value
    # is a register's, not a buffer's)
    wide, fused = [], False
    for line in compiled.as_text().splitlines():
        if line.startswith(("%fused_computation", "fused_computation")):
            fused = True
        elif line.startswith("}"):
            fused = False
        elif not fused:
            wide += re.findall(r"= f32\[([\d,]+)\]\S* [\w\-]+\(", line)
    assert wide                     # the scan found the float32 buffers
    shapes = _weight_shapes(params)
    widened = [dims for dims in wide
               if tuple(int(d) for d in dims.split(",")) in shapes]
    assert not widened, widened
