"""Compile-only checks of the ``solar_open2_250b_serve_c128`` cell's programs
at their real size, for a described TPU v5e (the TPU compiler runs here;
nothing executes): what the decode and prefill programs hold, that the
per-slot delta-rule state is updated in place (no second buffer of its size
among the temporaries) and that bfloat16 weights stay bfloat16 inside them.
``memory_analysis()`` bytes are printed (``pytest -s``) for PERF.md."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib import cell as cells

# the described chip, the cache switch and the weight shapes as the other
# served shares' compile-only tests have them
from test_bench_granite_compiles import _nbytes
from test_bench_longcat_compiles import (  # noqa: F401
    HBM, _weight_shapes, topo, uncached)

CELL = "solar_open2_250b_serve_c128"


def _programs(topo, monkeypatch):
    from horovod_tpu.ops.pallas import flash_attention as fa
    from horovod_tpu.serving import engine as eng, kv_cache as kvc

    from benchmarks.families import solar_open2 as fam
    # the decode step's attention layer goes through the paged-decode
    # kernel on a TPU; ``enabled()`` keys on the default backend (the CPU)
    monkeypatch.setattr(fa, "enabled", lambda: True)
    cell = cells.load_cell(CELL)
    e = cell.traffic["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    cfg = fam.program_config(cell.config)
    model = eng.serve_model(cfg)
    pages_per_slot = e["max_seq"] // e["page"]
    rows = model.cache_rows(cfg)
    pool = kvc.PagePool(rows[0].blocks, e["slots"] * pages_per_slot,
                        e["page"], dtype=cfg.dtype, rows=rows)

    def shaped(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = shaped(jax.eval_shape(
        lambda: fam.weights(cell.config, jax.random.PRNGKey(0))))
    slot_state = shaped(model.slot_state(cfg, e["slots"]))
    held = tuple(jax.ShapeDtypeStruct(s, cfg.dtype, sharding=one)
                 for s in pool.shapes()) + tuple(shaped(model.state(cfg))) \
        + tuple(slot_state)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    jits = eng.serve_programs(
        cfg, [kvc.pool_format(one, len(s)) for s in pool.shapes()])
    slots = e["slots"]
    return params, pool, slot_state, {
        "decode": lambda: jits["decode"].lower(
            params, *held, i32(slots, pages_per_slot), i32(slots),
            i32(slots)).compile(),
        "prefill": lambda: jits["prefill"].lower(
            params, *held, i32(pages_per_slot), i32(), i32(), i32(),
            i32(e["prefill_chunk"])).compile()}


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_the_cells_programs_fit_and_keep_the_state_in_place(
        topo, uncached, monkeypatch, name):
    params, pool, slot_state, programs = _programs(topo, monkeypatch)
    compiled = programs[name]()
    m = compiled.memory_analysis()
    weights, state = _nbytes(params), _nbytes(slot_state)
    print(f"\n{CELL} {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} "
          f"GB (weights {weights / 1e9:.3f}, slot state {state / 1e9:.3f}, "
          f"K/V pages {pool.nbytes() / 1e9:.3f}), temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    # 3.308 B parameters in bfloat16 (the routers' 5.2 M in float32)
    assert weights == pytest.approx(6.617e9 + 0.011e9, rel=2e-3)
    # 128 slots x 3 layers x (64 x 128 x 128 + 3 x 24576) x 4 B
    assert state == 128 * 3 * (64 * 128 * 128 + 3 * 24576) * 4
    # and 128 x 2048 tokens of K and V rows of 8 x 128 in bfloat16 (and
    # the scratch page)
    assert pool.nbytes() == pytest.approx(1.074e9, rel=2e-3)
    assert m.argument_size_in_bytes == pytest.approx(9.42e9, rel=0.02)
    # a second buffer of the state's size (1.72 GB), or of one layer's
    # weights, among the temporaries fails this
    assert m.temp_size_in_bytes <= 1.0e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 0.85 * HBM
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
