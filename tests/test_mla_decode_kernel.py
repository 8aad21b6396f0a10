"""The paged latent decode kernel (``ops/pallas/mla_decode.py``,
``hvd_mla_decode``) against its spec, the absorbed attention over each slot's
gathered pages (``models/mla.mla_attend_absorbed``), in interpret mode on the
CPU: ragged lengths (an empty slot on the scratch table, one token, either
side of a page boundary, a full table and a slot at ``max_seq``), pages in
shuffled physical order, YaRN on and off, float32 and bfloat16, pages a step
that divide the table and that do not; that it reads no page past a slot's
length; and the engine's count of the pages a decode step walks, on a LongCat
engine serving through both kernels as the reference does."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.config import knobs
from horovod_tpu.models import kimi_k2 as kk
from horovod_tpu.models import mla
from horovod_tpu.ops.pallas import mla_decode, mla_prefill
from horovod_tpu.serving import ServeEngine
from horovod_tpu.serving import kv_cache as kvc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_kimi_k2 import _cfg                               # noqa: E402
from test_longcat_flash import _cfg as _lc_cfg              # noqa: E402
from test_longcat_flash import _params as _lc_params        # noqa: E402
from test_longcat_flash import (                            # noqa: E402
    _reference_logits as _lc_reference_logits)

PAGE, N_MAX = 128, 8
MAX_SEQ = PAGE * N_MAX
# an empty slot (the scratch table), one token, either side of a page
# boundary, the table's last position, and a slot at max_seq (its row went
# to the scratch page; it sees every page)
LENGTHS = (0, 1, 127, 128, 129, MAX_SEQ - 1, MAX_SEQ)


def _pool_and_step(cfg, dtype, lengths=LENGTHS, seed=0):
    """A flat pool of 2 blocks' pages; each slot's block table over block 1,
    every entry its own page in shuffled physical order (an empty slot's the
    scratch page's); each slot's cached rows ``0 .. lengths[n]`` projected
    from random inputs and written as the engine writes them (the prompt a
    chunk, the step's own row as ``decode_body`` writes it); the step's
    queries: (cfg, flat, bt ``[N, n_max]``, scratch, lengths, q_nope, q_rope,
    params of the block)."""
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = kk.init_params(cfg, jax.random.PRNGKey(seed))
    bp = jax.tree.map(lambda a: a[1], params["layers"]["mla"])
    n = len(lengths)
    n_phys = n * N_MAX + 1
    pool = jnp.zeros((2, n_phys, PAGE, cfg.cache_row), dtype)
    order = np.random.default_rng(seed).permutation(n_phys - 1)
    tables = order.reshape(n, N_MAX).astype(np.int32)
    ln = np.asarray(lengths, np.int32)
    tables[ln == 0] = n_phys - 1
    bt, scratch = kvc.block_pages(pool.shape, jnp.int32(1),
                                  jnp.asarray(tables))
    flat, = kvc.flat_pool(pool)
    q_nope, q_rope, new = [], [], []
    for s, length in enumerate(lengths):
        x = jax.random.normal(jax.random.PRNGKey(seed + 1 + s),
                              (length + 1, cfg.d_model), jnp.float32)
        qn, qr, rows = mla.mla_project(cfg, bp, x.astype(dtype),
                                       jnp.arange(length + 1, dtype=jnp.int32))
        if length:
            flat, = kvc.write_chunk_rows((flat,), (rows[:length],), bt[s],
                                         jnp.int32(0), jnp.int32(length),
                                         scratch=scratch)
        q_nope.append(qn[-1])
        q_rope.append(qr[-1])
        new.append(rows[-1])
    lengths = jnp.asarray(ln)
    flat, = kvc.write_token_rows((flat,), (jnp.stack(new),), bt, lengths,
                                 valid=lengths < MAX_SEQ, scratch=scratch)
    return (cfg, flat, bt, scratch, lengths, jnp.stack(q_nope),
            jnp.stack(q_rope), bp)


def _spec(cfg, bp, q_nope, q_rope, flat, bt, lengths):
    visible = jnp.arange(MAX_SEQ)[None, :] <= lengths[:, None]
    return mla._gathered(mla.mla_attend_absorbed)(
        cfg, bp, q_nope, q_rope, flat, bt, visible)


def _paged(cfg, bp, q_nope, q_rope, flat, bt, lengths):
    return mla.mla_attend_paged_decode(cfg, bp, q_nope, q_rope, flat, bt,
                                       lengths, interpret=True)


@pytest.fixture(params=[8, 4, 3])
def pages_per_step(request, monkeypatch):
    """Steps of 8 pages (the kernel's own: the table is one step), of 4 (2
    steps) and of 3 (3 steps, the last holding 2 pages of the table and a
    repeat)."""
    monkeypatch.setattr(mla_decode, "mla_decode", functools.partial(
        mla_decode.mla_decode, pages_per_step=request.param))
    return request.param


@pytest.mark.parametrize("yarn", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_the_gathered_spec(pages_per_step, yarn, dtype):
    cfg = _cfg() if yarn else _cfg(rope_scaling=None)
    cfg, flat, bt, _, lengths, q_nope, q_rope, bp = _pool_and_step(cfg, dtype)
    want = np.asarray(_spec(cfg, bp, q_nope, q_rope, flat, bt, lengths),
                      np.float32)
    got = np.asarray(_paged(cfg, bp, q_nope, q_rope, flat, bt, lengths),
                     np.float32)
    assert got.shape == (len(LENGTHS), cfg.n_heads * cfg.v_dim)
    assert np.isfinite(got).all()       # the empty slot too
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for s, length in enumerate(LENGTHS):
        np.testing.assert_allclose(got[s], want[s],
                                   atol=tol * np.abs(want).max(), rtol=0,
                                   err_msg=f"length {length}")


def test_the_kernel_reads_no_dead_page(pages_per_step):
    """Every page past those a slot can see and every page outside the
    tables hold NaN (the scratch page only the empty slot sees keeps its
    row): the output is the same, bit for bit."""
    cfg, flat, bt, scratch, lengths, q_nope, q_rope, bp = _pool_and_step(
        _cfg(), jnp.float32)
    clean = _paged(cfg, bp, q_nope, q_rope, flat, bt, lengths)
    keep = np.zeros((flat.shape[0],), bool)
    for s, length in enumerate(LENGTHS):
        keep[np.asarray(bt[s, :min(length // PAGE, N_MAX - 1) + 1])] = True
    assert keep[int(scratch)] and keep.sum() < flat.shape[0] // 2
    dirty = jnp.where(jnp.asarray(keep)[:, None, None], flat, jnp.nan)
    got = _paged(cfg, bp, q_nope, q_rope, dirty, bt, lengths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("n_max", [1, 5, 8, 100])
@pytest.mark.parametrize("g", [1, 3, 4, 8])
def test_the_index_maps_name_no_page_past_the_last_and_repeat_after_it(
        n_max, g):
    """``mla_prefill.page_index`` for every step of a walk over pages ``0 ..
    last``: each input names only those, names each live page of its own
    once (``j*g + i``), and after its last live page names it again, so its
    block index never changes once the walk is past it."""
    g = min(g, n_max)
    steps = -(-n_max // g)
    for last in range(n_max):
        walked = set()
        for i in range(g):
            named = [int(mla_prefill.page_index(j, i, g, jnp.int32(last)))
                     for j in range(steps)]
            assert max(named) <= last, (last, i, named)
            live = [p for p in range(i, last + 1, g)]
            assert named[:len(live)] == live
            assert set(named[len(live):]) <= {named[max(len(live) - 1, 0)]}
            walked |= set(named)
        assert walked == set(range(last + 1))


def test_the_engine_counts_the_pages_a_decode_step_walks():
    """``engine.stats()["decode_pages"]``, on a small LongCat engine serving
    through both kernels in interpret mode: per step, over the slots that
    decode, the pages that hold a slot's cached tokens and its new one (what
    the kernel's grid walks) and every slot's block table. The tokens served
    (one slot of three left empty) are the reference's greedy ones, and the
    decode program runs the kernel."""
    knobs.set_override("HOROVOD_TPU_PALLAS", "interpret")
    try:
        cfg = _lc_cfg(expert_first=2, expert_count=4)
        params = _lc_params(cfg)
        eng = ServeEngine(cfg, params, None, slots=3, page=8, max_seq=64,
                          prefill_chunk=32, prefix_cache=False, draft="off")
        assert eng.stats()["decode_pages"] == {
            "steps": 0, "walked": 0, "table": 0}
        rng = np.random.default_rng(4)
        seqs = {}
        tokens = np.zeros((eng.slots,), np.int32)
        for n in (7, 16):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            slot = eng.reserve(n + 6)
            tokens[slot] = eng.prefill(slot, prompt)
            seqs[slot] = list(prompt)
        walked = 0
        for _ in range(3):
            walked += sum(-(-(len(seq) + 1) // 8) for seq in seqs.values())
            for slot in seqs:
                seqs[slot].append(int(tokens[slot]))
            tokens = eng.decode_step(tokens)
        text = eng.executable_text("serve_decode")
        stats = eng.stats()["decode_pages"]
    finally:
        knobs.clear_override("HOROVOD_TPU_PALLAS")
    assert stats == {"steps": 3, "walked": walked, "table": 3 * 3 * 8}
    assert walked == (1 + 3) + (2 + 3) + (2 + 3)
    assert "hvd_mla_decode" in text
    for slot, seq in seqs.items():
        seq = seq + [int(tokens[slot])]
        want = _lc_reference_logits(cfg, params, np.array(seq[:-1], np.int32))
        n = len(seq) - 4
        assert seq[n:] == [int(t) for t in np.argmax(want[n - 1:], axis=-1)]
