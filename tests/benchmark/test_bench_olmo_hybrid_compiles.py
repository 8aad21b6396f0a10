"""Compile-only checks at the cells' real size, for a described TPU v5e (the
TPU compiler runs here; nothing executes). The ``olmo_hybrid_7b_serve_c64_p2k``
cell's decode and 256-row prefill programs: what they hold, that the per-slot
delta-rule state is updated in place and stays float32 (no second buffer of
its size among the temporaries, no pool copied for a gather), that bfloat16
weights stay bfloat16. And the two hybrid cells that share the step and the
delta rule it changed: Granite's and Solar's programs compile to the text
their tree compiled before the shared rule (``data/hybrid_programs_v5e.json``:
the parent's readings), once metadata and instruction names are taken out.
``memory_analysis()`` bytes are printed (``pytest -s``) for PERF.md."""

import collections
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib import cell as cells

# the described chip, the cache switch and the weight shapes as the other
# served shares' compile-only tests have them
from test_bench_longcat_compiles import (  # noqa: F401
    HBM, _weight_shapes, topo, uncached)

CELL = "olmo_hybrid_7b_serve_c64_p2k"
FAMILIES = {CELL: "olmo_hybrid",
            "granite_4_0_h_small_serve_c64": "granite_hybrid",
            "solar_open2_250b_serve_c128": "solar_open2"}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _programs(topo, monkeypatch, cell_name):
    from horovod_tpu.ops.pallas import flash_attention as fa
    from horovod_tpu.serving import engine as eng, kv_cache as kvc

    fam = importlib.import_module("benchmarks.families."
                                  + FAMILIES[cell_name])
    # the decode step's attention layers go through the paged-decode kernel
    # on a TPU; ``enabled()`` keys on the default backend (the CPU)
    monkeypatch.setattr(fa, "enabled", lambda: True)
    cell = cells.load_cell(cell_name)
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


def _nbytes(tree):
    return sum(int(np.prod(s.shape)) * s.dtype.itemsize
               for s in jax.tree.leaves(tree))


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_the_cells_programs_fit_and_keep_the_state_in_place(
        topo, uncached, monkeypatch, name):
    params, pool, slot_state, programs = _programs(topo, monkeypatch, CELL)
    compiled = programs[name]()
    m = compiled.memory_analysis()
    weights, state = _nbytes(params), _nbytes(slot_state)
    print(f"\n{CELL} {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} "
          f"GB (weights {weights / 1e9:.3f}, slot state {state / 1e9:.3f}, "
          f"K/V pages {pool.nbytes() / 1e9:.3f}), temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    # 2.436 B parameters, in bfloat16 but the small leaves' 0.34 M
    assert weights == pytest.approx(4.871e9, rel=2e-3)
    # 64 slots x 6 layers x (30 x 96 x 192 + 3 x 11520) x 4 B, float32
    assert state == 64 * 6 * (30 * 96 * 192 + 3 * 11520) * 4
    assert all(s.dtype == jnp.float32 for s in jax.tree.leaves(slot_state))
    # 64 x 3072 tokens of K and V rows of 30 x 128 in 2 layers, bfloat16
    # (and the scratch pages)
    assert pool.nbytes() == pytest.approx(6.040e9, rel=2e-3)
    # about 11.8 GB, every array at its own size: the state's heads of 192
    # values lie in pairs on 384 lanes (one by one on 256 they would add
    # 0.28 GB)
    assert m.argument_size_in_bytes == pytest.approx(
        weights + state + pool.nbytes(), rel=1e-4)
    assert m.argument_size_in_bytes == pytest.approx(11.82e9, rel=0.005)
    # a second buffer of the state's size (0.85 GB), a layer's tails laid
    # three rows to the lanes (2.1 GB) or the pool copied for a page gather
    # (3 GB) among the temporaries fails this
    assert m.temp_size_in_bytes <= 0.2e9
    assert "mini-gather" not in compiled.as_text()
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


def _normal(text):
    """A compiled text without what moves with a source line or the order
    in which the tracer made the instructions: the tables of files and
    stack frames, metadata, backend configuration, and every instruction's
    and computation's name replaced by its rank of first appearance."""
    out = []
    for line in text.splitlines():
        if re.match(r"^(\d+ |FileNames|FunctionNames|FileLocations|"
                    r"StackFrames)", line):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        line = re.sub(r", backend_config=\{.*\}$", "", line)
        line = re.sub(r", frontend_attributes=\{[^}]*\}", "", line)
        out.append(line.rstrip())
    names = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  "\n".join(out))


@pytest.mark.parametrize("name", ["decode", "prefill"])
@pytest.mark.parametrize("cell_name", ["granite_4_0_h_small_serve_c64",
                                       "solar_open2_250b_serve_c128"])
def test_the_other_hybrids_programs_are_their_parents_text(
        topo, uncached, monkeypatch, cell_name, name):
    """The shared step (a dense SwiGLU, the norm's place and the q/k norms
    read where the stack has them) and the shared delta rule change neither
    Granite's nor Solar's programs: the same text, the same opcodes, the
    same memory figures (Solar's 9.426 + 0.071 GB decode, 9.426 + 0.205 GB
    256-prefill)."""
    with open(os.path.join(DATA, "hybrid_programs_v5e.json")) as f:
        was = json.load(f)[f"{cell_name}.{name}"]
    compiled = _programs(topo, monkeypatch, cell_name)[-1][name]()
    text = _normal(compiled.as_text())
    ops = collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", text, re.M))
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes, m.temp_size_in_bytes) == (
        was["argument_bytes"], was["temp_bytes"])
    assert dict(sorted(ops.items())) == was["opcodes"]
    assert hashlib.sha256(text.encode()).hexdigest() == was["sha256"]
