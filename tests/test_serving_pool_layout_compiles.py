"""Compile-only checks of the serve engine's programs at the serve cell's
real size (pythia-410m widths, 16 and 24 slots of 2048 context, pages of
128, bf16) for a described ``v5e:2x2``: the KV pool stays in one layout and
one buffer, its pages are whole tiles (a token's 16 heads of 64 side by side
on 1024 lanes: the arguments hold the pool's own bytes, no padding), and the
programs the engine lowers from the tree it prepared hold no float32 buffer
the shape of a weight. The programs are built the
way the engine builds them (``serve_programs`` over ``pool_format``,
lowered from shapes); nothing executes. Bytes are printed (``pytest -s``)
for PERF.md."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import engine as eng, kv_cache as kvc

PAGE, CTX, BUCKET = 128, 2048, 256
ROW_MAJOR = (0, 1, 2, 3)


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


def _cfg():
    return tfm.TransformerConfig(
        vocab_size=50304, d_model=1024, n_heads=16, head_dim=64,
        n_layers=24, d_ff=4096, max_seq=CTX, dtype=jnp.bfloat16,
        dp_axis=None, remat=False)


def _compile(topo, program, slots, prepared=False):
    """(compiled, pool) of one engine program over a pool of ``slots``
    slots, on the first described chip: from the float32 tree's shapes,
    or (``prepared``) from the shapes of the tree the engine makes of it
    at build."""
    cfg = _cfg()
    one = SingleDeviceSharding(topo.devices[0])
    pool = kvc.PagePool(cfg.n_layers, slots * (CTX // PAGE), PAGE,
                        cfg.n_heads, cfg.head_dim, dtype=cfg.dtype)
    k = jax.eval_shape(pool.alloc_arrays)[0]
    kv = jax.ShapeDtypeStruct(k.shape, k.dtype, sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    if prepared:
        params = eng.serve_model(cfg).served_params(cfg, params)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    n_max = CTX // PAGE
    args = {
        "decode": (params, kv, kv, i32(slots, n_max), i32(slots), i32(slots)),
        "prefill": (params, kv, kv, i32(n_max), i32(), i32(), i32(BUCKET)),
        "draft": (params, kv, kv, i32(slots, n_max), i32(slots), i32(slots)),
        "cow": (kv, kv, i32(), i32()),
    }[program]
    jits = eng.serve_programs(cfg, kvc.pool_format(one), draft_layers=2)
    return jits[program].lower(*args).compile(), pool


def _pool_sized(text, layer_elems, opcodes):
    """Instructions of the optimized HLO with one of ``opcodes`` whose
    result is a layer's pool or more: (opcode, result shape) of each."""
    found = []
    for m in re.finditer(
            r"= \(?(\w+)\[([\d,]*)\][^ ]* (%s)\(" % "|".join(opcodes), text):
        dims = [int(d) for d in m.group(2).split(",") if d]
        if int(np.prod(dims)) >= layer_elems:
            found.append((m.group(3), f"{m.group(1)}[{m.group(2)}]"))
    return found


@pytest.mark.parametrize("program,slots", [
    ("decode", 16), ("decode", 24), ("prefill", 16), ("prefill", 24),
    ("draft", 16), ("cow", 16)])
def test_pool_stays_in_one_layout_and_one_buffer(topo, compiled_kernels,
                                                 program, slots):
    compiled, pool = _compile(topo, program, slots)
    m = compiled.memory_analysis()
    print(f"\nserve {program}, {slots} slots: arguments "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, pool "
          f"{pool.nbytes() / 1e9:.3f} GB unpadded")
    assert m.temp_size_in_bytes < pool.nbytes() / 4
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 16 * 2 ** 30
    text = compiled.as_text()
    layer_elems = ((pool.n_pages + 1) * pool.page * pool.n_kv_heads
                   * pool.head_dim)
    # no step copies, slices out or stacks back a layer's pool; the COW
    # program IS one dynamic-update-slice of a page per layer, in place
    opcodes = ("copy",) if program == "cow" else (
        "copy", "dynamic-slice", "dynamic-update-slice")
    assert _pool_sized(text, layer_elems, opcodes) == []
    # the pool goes in and comes out in the engine's one layout
    first = 0 if program == "cow" else 1
    formats = (list(compiled.input_formats[0][first:first + 2])
               + list(compiled.output_formats[:2]))
    assert [f.layout.major_to_minor for f in formats] == [ROW_MAJOR] * 4
    if program in ("decode", "draft"):
        from horovod_tpu.ops.pallas.flash_attention import \
            compiled_kernels as ck
        assert "hvd_paged_decode" in ck(text)
        # the kernel reads the flat run of pages itself, row-major: a
        # page is [128, 16 * 64] bfloat16, whole (16, 128) tiles
        calls = [line for line in text.splitlines()
                 if "%hvd_paged_decode" in line
                 and "tpu_custom_call" in line]
        assert len(calls) == 1
        operands = calls[0].split("operand_layout_constraints=")[1]
        pages = pool.n_layers * (pool.n_pages + 1)
        assert operands.count(
            f"bf16[{pages},{PAGE},1024]{{2,1,0}}") == 2, operands[:400]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_prepared_programs_hold_no_float32_weight(topo, compiled_kernels,
                                                  program):
    """The cell's decode and 256-token prefill programs as the engine
    lowers them, from the shapes of the tree it prepared: 0.81 GB of
    bfloat16 weights beside the 3.23 GB pool, which lies there at its own
    size (4.04 GB of arguments; 7.28 while a head of 64 stood alone on
    128 lanes), and no float32 buffer the shape of a weight stack or of
    one layer's slice of it."""
    cfg = _cfg()
    given = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    served = eng.serve_model(cfg).served_params(cfg, given)
    named = lambda t: {**{k: v for k, v in t.items() if k != "layers"},
                       **t["layers"]}
    cast = sorted(n for n, a in named(served).items()
                  if a.dtype != named(given)[n].dtype)
    assert cast == sorted(["embed", "head", "wq", "wk", "wv", "wo", "w_in",
                           "w_out"])
    assert all(named(served)[n].dtype == jnp.bfloat16 for n in cast)
    nbytes = lambda t: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    compiled, pool = _compile(topo, program, 16, prepared=True)
    m = compiled.memory_analysis()
    print(f"\nserve {program} from the prepared tree, 16 slots: arguments "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB (weights "
          f"{nbytes(served) / 1e9:.3f} of {nbytes(given) / 1e9:.3f} given), "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB")
    assert nbytes(given) == pytest.approx(1.62e9, rel=5e-3)
    assert nbytes(served) == pytest.approx(0.81e9, rel=5e-3)
    assert pool.nbytes() == pytest.approx(3.234e9, rel=1e-3)
    assert m.argument_size_in_bytes == pytest.approx(
        nbytes(served) + pool.nbytes(), rel=2e-3)      # 4.044 GB
    assert m.temp_size_in_bytes < 0.05e9     # 0.605 were the cast copies
    shapes = set()                  # the norm scales stay float32
    for a in (named(given)[n] for n in cast):
        shapes |= {tuple(a.shape), tuple(a.shape[1:])}
    shapes = {s for s in shapes if len(s) >= 2}
    wide, fused = [], False
    for line in compiled.as_text().splitlines():
        if line.startswith(("%fused_computation", "fused_computation")):
            fused = True
        elif line.startswith("}"):
            fused = False
        elif not fused:
            wide += re.findall(r"= f32\[([\d,]+)\]\S* [\w\-]+\(", line)
    assert wide                     # the scan found the float32 buffers
    widened = [dims for dims in wide
               if tuple(int(d) for d in dims.split(",")) in shapes]
    assert not widened, widened


def test_engine_counts_the_pool_as_it_lies_in_memory():
    """``engine.stats()["pool"]``: each array's row, the arrays' bytes,
    and what the decode program's arguments hold beyond weights, state
    and integers. On the CPU nothing is tiled, so the ratio is 1.0 (on
    the chip it is what the lanes leave: 1.0 for the flat row, 2.0 while
    a head of 64 was a row of its own)."""
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, head_dim=16, n_layers=2,
        d_ff=128, max_seq=256, dtype=jnp.float32, dp_axis=None, remat=False)
    engine = eng.ServeEngine(
        cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), mesh=None,
        slots=4, page=16, max_seq=128, prefill_chunk=64)
    held = engine.stats()["pool"]
    assert held["rows"] == {"k": [4 * 16], "v": [4 * 16]}
    assert held["bytes"] == engine.pool.nbytes() \
        == 2 * 2 * (4 * 8 + 1) * 16 * 64 * 4
    assert held["resident_bytes"] == held["bytes"]
    assert held["padding"] == 1.0
    assert engine.stats()["program_argument_bytes"]["serve_decode"] > \
        held["bytes"]
