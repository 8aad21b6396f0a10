"""Pallas TPU kernel of a latent-attention decode step over the paged cache
(``hvd_mla_decode``).

A decode step is the prefill kernel's computation (``mla_prefill``) with one
query position a slot: every head of slot ``n``, ``Wkvb_k`` absorbed into
its query, attends to the ONE latent row ``[c | k_r]`` a cached token holds,
at positions ``0 .. lengths[n]`` (the step writes the new token's row before
it attends)::

    q   = [q_nope Wkvb_k^T | q_rope]          [N*H, rank + rope]  (caller)
    s   = q rows^T * scale                    float32
    o   = softmax(s) c                        [N*H, rank]
    out = o Wkvb_v                            (caller)

Grid ``(slots, steps)``: a slot's ``H`` query rows are one block; a step
takes ``pages_per_step`` pages of the slot's block table (scalar-prefetched
with the lengths) one under another in VMEM, one ``[H, rank + rope] x [rank
+ rope, keys]`` product for the scores and one ``[H, keys] x [keys, rank]``
for the sums under the walk both kernels share (``mla_prefill.walk``). Slot
``n`` walks its pages ``0 .. lengths[n] // page`` and no further: the index
maps repeat the last live page after it, so nothing more is fetched, and a
step with no page to see does not run. No row of the block table's
``max_seq`` positions is gathered, masked or written back to HBM.

An empty slot (length 0, the scratch table) sees key 0 of its scratch page,
the row its own write left there: finite, and discarded by the caller. A
slot at ``lengths == max_seq`` (its write went to the scratch page) sees
every page of its table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas.mla_prefill import (
    compiler_kwargs, page_index, walk)

# Pages a grid step. One attention block of the Kimi cell's decode step on a
# TPU v5e (32 slots, 100-page tables, lengths from the cell's strata with 4
# slots empty, 1283 of 3200 pages live; ms a call over 20 calls in a row,
# with the absorption's products around the kernel): 2 pages 0.832 ms, 4
# 0.662, 8 0.607, 16 0.628; every page live 1.004 (4) and 0.865 (8); no page
# live 0.416 (4) and 0.439 (8); the kernel compiled in 0.45 s at 4, 0.74 s
# at 8. The gathered, masked attention it replaces 4.853 ms, with 1.05 GB of
# temporaries.
_PAGES_PER_STEP = 8


def _decode_kernel(bt_ref, len_ref, q_ref, *refs, scale: float, rank: int,
                   n_ctx: int, precision):
    *page_refs, o_ref, m_scr, l_scr, acc_scr = refs
    # a slot at max_seq sees its whole table and no key past it
    seen = jnp.minimum(len_ref[pl.program_id(0)], n_ctx - 1)
    walk(q_ref, page_refs, o_ref, m_scr, l_scr, acc_scr, seen=seen,
         visible=None, scale=scale, rank=rank, precision=precision)


@functools.partial(jax.jit, static_argnames=(
    "rank", "scale", "pages_per_step", "interpret"))
def mla_decode(q: jax.Array, pages: jax.Array, block_tables: jax.Array,
               lengths: jax.Array, *, rank: int, scale: float,
               pages_per_step: int = _PAGES_PER_STEP,
               interpret: bool = False) -> jax.Array:
    """Every slot's query rows over its own cached latent rows -> ``o``
    ``[N*H, rank]`` in the dtype of ``q``, normalised.

    q ``[N*H, rank + rope]`` (row ``n*H + h``: slot ``n``, head ``h``,
    ``Wkvb_k`` absorbed); pages ``[n_pages, page, rank + rope]`` (the flat
    pool, ``kv_cache.flat_pool``); block_tables ``[N, n_max]`` int32 page
    ids (``kv_cache.block_pages``: this block's pages); lengths ``[N]``
    int32, the position of each slot's new token, whose row the step has
    written before this call."""
    rows, width = q.shape
    n, n_max = block_tables.shape
    heads = rows // n
    page = pages.shape[1]
    g = max(1, min(pages_per_step, n_max))
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else None)
    kernel = functools.partial(_decode_kernel, scale=float(scale), rank=rank,
                               n_ctx=n_max * page, precision=precision)

    def page_spec(i):
        def index(s, j, bt, ln):
            last = jnp.minimum(ln[s] // page, n_max - 1)
            return bt[s, page_index(j, i, g, last)], 0, 0
        return pl.BlockSpec((1, page, width), index)

    rows_spec = lambda w: pl.BlockSpec((heads, w),
                                       lambda s, j, bt, ln: (s, 0))
    return pl.pallas_call(
        kernel,
        name="hvd_mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, -(-n_max // g)),
            in_specs=[rows_spec(width)] + [page_spec(i) for i in range(g)],
            out_specs=rows_spec(rank),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),     # m
                pltpu.VMEM((heads, 1), jnp.float32),     # l
                pltpu.VMEM((heads, rank), jnp.float32),  # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, rank), q.dtype),
        interpret=interpret,
        **compiler_kwargs(interpret),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
      *([pages] * g))
