"""The paged latent prefill kernel (``ops/pallas/mla_prefill.py``,
``hvd_mla_prefill``) against its spec, the keys and values expanded from the
gathered pages (``models/mla.mla_attend_expanded``), in interpret mode on the
CPU: chunks that start at 0, mid-page and on a page boundary, padding rows,
pages in shuffled physical order, YaRN on and off, float32 and bfloat16; that
it reads no page past those a chunk can see; the engine's count of the pages
walked; and, compile-only for a described v5e at the latent cells' real
sizes, that every prefill bucket holds the kernel and no float32 score over
the block table, and that the decode programs hold the decode kernel
(``tests/test_mla_decode_kernel.py``) and gather no block table."""

import dataclasses
import functools
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.config import knobs
from horovod_tpu.models import kimi_k2 as kk
from horovod_tpu.models import mla
from horovod_tpu.ops.pallas import flash_attention, mla_prefill
from horovod_tpu.serving import kv_cache as kvc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the small Kimi K2 config, its weights, engine and reference; the engine's
# own program calls and the compiled text's normalisation; the described
# v5e and the kernels' dispatch turned on for it
from test_kimi_k2 import (                                  # noqa: E402
    SMALL, _cfg, _engine, _params, _reference_logits)
from test_longcat_flash import (                            # noqa: E402
    _decode_logits, _prefill_logits)
from test_serving_pool_layout_compiles import (             # noqa: E402,F401
    compiled_kernels, topo)

PAGE, N_MAX, C = 8, 8, 16


def _pool_and_chunk(cfg, start, dtype, seed=0):
    """A flat pool of 2 blocks' pages, one sequence's block table over
    block 1 in shuffled physical order, the cached rows of positions
    ``0 .. start + C`` projected from random inputs (the chunk's own rows
    written too, as the step writes them before it attends), and the
    chunk's queries: (flat, bt, q_nope, q_rope, params of the block)."""
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = kk.init_params(cfg, jax.random.PRNGKey(seed))
    bp = jax.tree.map(lambda a: a[1], params["layers"]["mla"])
    n_phys = N_MAX * 2 + 1
    pool = jnp.zeros((2, n_phys, PAGE, cfg.cache_row), dtype)
    order = np.random.default_rng(seed).permutation(n_phys - 1)[:N_MAX]
    bt, scratch = kvc.block_pages(pool.shape, jnp.int32(1),
                                  jnp.asarray(order, jnp.int32))
    flat, = kvc.flat_pool(pool)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (start + C, cfg.d_model), jnp.float32)
    pos = jnp.arange(start + C, dtype=jnp.int32)
    q_nope, q_rope, rows = mla.mla_project(cfg, bp, x.astype(dtype), pos)
    flat, = kvc.write_chunk_rows((flat,), (rows,), bt, jnp.int32(0),
                                 jnp.int32(start + C), scratch=scratch)
    return cfg, flat, bt, scratch, q_nope[start:], q_rope[start:], bp


def _spec(cfg, bp, q_nope, q_rope, flat, bt, start):
    pos = start + jnp.arange(C, dtype=jnp.int32)
    visible = jnp.arange(N_MAX * PAGE)[None, :] <= pos[:, None]
    return mla._gathered(mla.mla_attend_expanded)(
        cfg, bp, q_nope, q_rope, flat, bt, visible)


def _paged(cfg, bp, q_nope, q_rope, flat, bt, start, n_real):
    return mla.mla_attend_paged(cfg, bp, q_nope, q_rope, flat, bt,
                                jnp.int32(start), jnp.int32(n_real),
                                interpret=True)


@pytest.fixture(params=["one_block", "blocks_of_2_positions"])
def blocking(request, monkeypatch):
    """The kernel's own blocking (the whole chunk one query block at this
    size, a step of 4 pages), or query blocks of 2 positions and steps of
    3 pages (several blocks, a table that does not divide into steps)."""
    if request.param == "blocks_of_2_positions":
        monkeypatch.setattr(mla_prefill, "mla_prefill", functools.partial(
            mla_prefill.mla_prefill, block_rows=2 * SMALL["n_heads"],
            pages_per_step=3))
    return request.param


@pytest.mark.parametrize("yarn", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("start,n_real", [
    (0, 16),        # the prompt's first chunk
    (13, 16),       # starts mid-page
    (24, 11),       # on a page boundary, 5 padding rows
    (37, 2),        # mid-page, 14 padding rows
])
def test_kernel_matches_the_expanded_spec(blocking, yarn, dtype, start,
                                          n_real):
    cfg = _cfg() if yarn else _cfg(rope_scaling=None)
    cfg, flat, bt, _, q_nope, q_rope, bp = _pool_and_chunk(cfg, start, dtype)
    want = np.asarray(_spec(cfg, bp, q_nope, q_rope, flat, bt, start),
                      np.float32)[:n_real]
    got = np.asarray(_paged(cfg, bp, q_nope, q_rope, flat, bt, start,
                            n_real), np.float32)
    assert got.shape == (C, cfg.n_heads * cfg.v_dim)
    assert np.isfinite(got).all()       # padding rows too
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:n_real], want,
                               atol=tol * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("start,n_real", [(0, 16), (13, 9), (40, 16)])
def test_the_kernel_reads_no_dead_page(blocking, start, n_real):
    """Every page past those the chunk can see, the scratch page and every
    page outside the table hold NaN: the output is the same, bit for
    bit."""
    cfg, flat, bt, scratch, q_nope, q_rope, bp = _pool_and_chunk(
        _cfg(), start, jnp.float32)
    clean = _paged(cfg, bp, q_nope, q_rope, flat, bt, start, n_real)
    live = -(-(start + n_real) // PAGE)
    keep = np.zeros((flat.shape[0],), bool)
    keep[np.asarray(bt[:live])] = True
    assert not keep[int(scratch)]
    dirty = jnp.where(jnp.asarray(keep)[:, None, None], flat, jnp.nan)
    got = _paged(cfg, bp, q_nope, q_rope, dirty, bt, start, n_real)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_engine_serves_through_the_kernel_as_the_reference_does():
    """``ServeEngine`` with the kernels in interpret mode: chunked prefill
    (32 + 32 + 6 tokens over pages of 8) and decode, logits against the
    reference's one full pass, as the spec path is held to
    (``test_kimi_k2``); the prefill programs run the kernel."""
    knobs.set_override("HOROVOD_TPU_PALLAS", "interpret")
    try:
        cfg = _cfg(expert_first=8, expert_count=12)
        params = _params(cfg)
        eng = _engine(cfg, params)
        prompt = np.random.default_rng(0).integers(
            0, cfg.vocab_size, 70).astype(np.int32)
        slot = eng.reserve(90)
        chunks = _prefill_logits(eng, slot, prompt)
        seq, token = list(prompt), chunks[-1][1]
        got = {row: lg for row, _, lg in chunks}
        for _ in range(3):
            seq.append(token)
            toks = np.zeros((eng.slots,), np.int32)
            toks[slot] = token
            nxt, logits = _decode_logits(eng, toks)
            got[len(seq) - 1] = logits[slot]
            token = int(nxt[slot])
        text = eng.executable_text("serve_prefill_32")
    finally:
        knobs.clear_override("HOROVOD_TPU_PALLAS")
    assert "hvd_mla_prefill" in text
    want = _reference_logits(cfg, params, np.array(seq, np.int32))
    for row, lg in got.items():
        np.testing.assert_allclose(lg, want[row], atol=2e-4, rtol=2e-4)


def test_the_engine_counts_the_pages_a_chunk_walks():
    """``engine.stats()["prefill_pages"]``: per chunk the pages that hold
    its cached prefix and itself (what the kernel's grid walks) and the
    block table's width, summed over chunks."""
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg), prefill_chunk=16)
    assert eng.stats()["prefill_pages"] == {
        "chunks": 0, "walked": 0, "table": 0}
    rng = np.random.default_rng(3)
    walked = chunks = 0
    for n in (5, 16, 37):
        slot = eng.reserve(n + 1)
        eng.prefill(slot, rng.integers(0, cfg.vocab_size, n).astype(np.int32))
        for start in range(0, n, 16):
            chunks += 1
            walked += -(-min(start + 16, n) // 8)
        eng.release(slot)
    assert eng.n_max_pages == 128 // 8
    assert eng.stats()["prefill_pages"] == {
        "chunks": chunks, "walked": walked, "table": chunks * 16}
    assert (chunks, walked) == (5, 1 + 2 + 2 + 4 + 5)


# ---------------------------------------------------------------------------
# compile-only, for a described v5e, at the latent cells' real sizes
# ---------------------------------------------------------------------------

KIMI, LONGCAT = "kimi_k2_7_code_serve_c32_p12k", "longcat_flash_omni_serve_c64"


def _programs(topo, cell_name):
    """(engine config, {"decode": compile, bucket: compile}) of a cell's
    programs as the engine lowers them, from shapes alone."""
    from horovod_tpu.serving import engine as eng

    from benchmarks.lib import cell as cells
    cell = cells.load_cell(cell_name)
    fam = importlib.import_module(
        "benchmarks.families." + cell.config["family"])
    e = cell.traffic["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    cfg = fam.program_config(cell.config)
    model = eng.serve_model(cfg)
    pps = e["max_seq"] // e["page"]
    pool = kvc.PagePool(cfg.attention_blocks, e["slots"] * pps, e["page"],
                        dtype=cfg.dtype, rows=model.cache_rows(cfg))
    shaped = lambda tree: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)
    params = shaped(jax.eval_shape(
        lambda: fam.weights(cell.config, jax.random.PRNGKey(0))))
    held = tuple(jax.ShapeDtypeStruct(s, cfg.dtype, sharding=one)
                 for s in pool.shapes()) + tuple(shaped(model.state(cfg)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    jits = eng.serve_programs(
        cfg, [kvc.pool_format(one, len(s)) for s in pool.shapes()])
    slots = e["slots"]
    programs = {"decode": lambda: jits["decode"].lower(
        params, *held, i32(slots, pps), i32(slots), i32(slots)).compile()}
    for bucket in eng.prefill_buckets(e["prefill_chunk"]):
        programs[bucket] = functools.partial(
            lambda b: jits["prefill"].lower(
                params, *held, i32(pps), i32(), i32(), i32(b)).compile(),
            bucket)
    return e, programs


def test_kimi_prefill_buckets_run_the_kernel_and_score_nothing_over_the_table(
        topo, compiled_kernels):
    """Every prefill bucket of the Kimi cell holds ``hvd_mla_prefill`` (two
    instances: the dense run's and the expert run's) and no float32 buffer
    with the block table's 12 800 positions (the parent's 256-token chunk
    wrote ``f32[256, 64, 12800]``); temporaries are printed (``pytest
    -s``)."""
    e, programs = _programs(topo, KIMI)
    buckets = [b for b in programs if b != "decode"]
    assert buckets == [32, 64, 128, 256]
    for bucket in buckets:
        compiled = programs[bucket]()
        text = compiled.as_text()
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(f"\n{KIMI} prefill {bucket}: temporaries {temp / 1e6:.1f} MB")
        assert flash_attention.compiled_kernels(text) == {
            "hvd_mla_prefill": 2}, bucket
        assert not re.findall(r"f32\[[\d,]*\b%d\b" % e["max_seq"], text)
        assert temp < 0.05e9        # 0.869 GB at 256 in the parent


@pytest.mark.parametrize("cell_name", [KIMI, LONGCAT])
def test_decode_programs_run_the_kernel_and_gather_no_table(
        topo, compiled_kernels, cell_name):
    """Each latent cell's decode program holds ``hvd_mla_decode`` twice
    (Kimi K2: the dense run's and the expert run's; LongCat: a layer's two
    attention blocks in its one scan) and no operand with the block tables'
    ``max_seq`` positions (the gathered decode attention reads
    ``[slots, max_seq, 576]`` a block and masks it: ``bf16[32,12800,576]``
    in Kimi K2's cell, ``bf16[64,1024,576]`` in LongCat's); temporaries are
    printed (``pytest -s``)."""
    e, programs = _programs(topo, cell_name)
    compiled = programs["decode"]()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"\n{cell_name} decode: temporaries {temp / 1e6:.1f} MB")
    assert flash_attention.compiled_kernels(text) == {"hvd_mla_decode": 2}
    assert not re.findall(r"\[%d,%d\b" % (e["slots"], e["max_seq"]), text)
    assert temp < 0.05e9        # 1.05 GB with the gathered attention
