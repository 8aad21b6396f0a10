"""Pallas TPU kernel of a latent-attention prefill chunk over the paged cache
(``hvd_mla_prefill``).

The latent models (``models/mla.py``) cache one row a token and attention
block, ``[c | k_r]``: the normed latent ``c`` (``rank`` numbers) beside the
rotated key ``k_r`` that every head shares. With ``Wkvb`` absorbed into the
query and the output, as decode does (``mla_attend_absorbed``), every head of
every chunk position attends to that ONE row:

    q   = [q_nope Wkvb_k^T | q_rope]          [C*H, rank + rope]  (caller)
    s   = q rows^T * scale                    float32
    o   = softmax(s) c                        [C*H, rank]
    out = o Wkvb_v                            (caller)

so the chunk's heads stack into the rows of one matrix, and the cached rows
of a grid step (4 pages of 128 tokens) are one full-width product ``[rows,
576] x [576, 512]`` for the scores and one ``[rows, 512] x [512, 512]`` for
the sums, with one rescale of the accumulator. The kernel walks one
sequence's pages in place through its block table (scalar-prefetched,
``pltpu.PrefetchScalarGridSpec``) with the flash recurrence (running max,
sum and accumulator in VMEM), and only the pages a chunk can see: the cached
prefix and the chunk itself, which the step has written before it attends.
No score matrix over the block table and no expanded key or value reaches
HBM.

Grid ``(query blocks, steps)``: a query block is ``block_pos`` chunk
positions, every head of each (``block_pos * H`` rows, row ``r`` at position
``start + r // H``); a step takes ``pages_per_step`` pages of the table, one
under another in VMEM. A block walks its pages ``0 .. last`` where ``last``
holds the last position any of its rows may see; past it each of a step's
pages repeats the last live page it read (:func:`page_index`), whose block
index then does not change, so nothing more is fetched, and a step with no
page to see does not run. The walk (:func:`walk`) is the latent decode
kernel's too (``mla_decode``, ``hvd_mla_decode``). Every step that
runs applies the causal mask (a compare and a select over its scores: on the
chip as fast as a second, unmasked body for the steps under the diagonal,
and half the code for the compiler). Positions at or past ``start +
n_real`` are the bucket's padding: their rows see what the last real row
sees, and no row sees them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas.flash_attention import NEG_INF

_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b
# Query rows a grid step at most (positions x heads) and the cached pages it
# takes. One attention block of the Kimi cell on a TPU v5e (a 256-token
# chunk, 64 heads, 6 400 cached rows; ms a call over 10 calls in a row, and
# the kernel's compile): 2048 x 4 1.52 ms, 2.9 s; 1024 x 4 1.61 ms, 1.1 s;
# 1024 x 2 1.69 ms, 0.75 s; 512 x 4 1.82 ms, 1.0 s. The engine compiles a
# latent model's programs at every build (a reloaded program loses the
# pool's layout), so compile seconds are set-up seconds.
_BLOCK_ROWS = 1024
_PAGES_PER_STEP = 4
_VMEM_LIMIT = 96 * 2 ** 20


def _block_pos(c: int, heads: int, cap_rows: int = _BLOCK_ROWS) -> int:
    """Chunk positions a query block: the largest divisor of ``c`` whose
    rows (``* heads``) fit ``cap_rows``, at least 1."""
    for b in range(max(min(c, cap_rows // heads), 1), 0, -1):
        if c % b == 0:
            return b
    return 1


def _last_seen(qi, start, n_real, block_pos: int):
    """The last position a row of query block ``qi`` may see: its last
    position, or the last real one where the block reaches the padding."""
    return start + jnp.minimum((qi + 1) * block_pos, n_real) - 1


def walk(q_ref, page_refs, o_ref, m_scr, l_scr, acc_scr, *, seen, visible,
         scale: float, rank: int, precision) -> None:
    """The masked online-softmax walk of both latent kernels (this one and
    ``mla_decode``'s): grid axis 1 steps over a table's pages, ``len(
    page_refs)`` a step, one under another in VMEM. A step whose first key
    is past ``seen``, the last key any row of the block sees, does not run;
    a step that runs masks its scores to the keys at or before ``seen`` (a
    page past it is the clamped index map's repeat of a live one) for which
    ``visible(k)`` holds (``None``: all of them), then takes one product for
    the scores and one for the sums, with one rescale of the float32
    accumulator. The last step writes the normalised result."""
    j = pl.program_id(1)
    tq = q_ref.shape[0]
    keys = len(page_refs) * page_refs[0].shape[1]       # a step's keys
    k0 = j * keys

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(k0 <= seen)
    def _step():
        rows = jnp.concatenate([r[0] for r in page_refs], axis=0) \
            if len(page_refs) > 1 else page_refs[0][0]  # [keys, rank+rope]
        s = jax.lax.dot_general(
            q_ref[...], rows, _NT, precision=precision,
            preferred_element_type=jnp.float32) * scale   # [tq, keys]
        k = k0 + jax.lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
        mask = k <= seen
        if visible is not None:
            mask = visible(k) & mask
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                             # [tq, 1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next)          # page 0's first key: m is finite
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], _NN, precision=precision,
            preferred_element_type=jnp.float32)         # [tq, rank]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def page_index(j, i: int, g: int, last):
    """The table entry the ``i``-th of a step's ``g`` pages reads at step
    ``j`` of a walk that sees pages ``0 .. last``: page ``j*g + i`` while
    that is live; past it, the last live page that same input read (or
    ``last``, where it read none), so its block index repeats and nothing
    more is fetched."""
    return jnp.where(i <= last, jnp.minimum(j, (last - i) // g) * g + i,
                     last)


def compiler_kwargs(interpret: bool) -> dict:
    """Both latent kernels' compiler parameters: the first grid axis
    independent blocks, the second the walk; VMEM for the largest tiling."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _prefill_kernel(bt_ref, pos_ref, q_ref, *refs, scale: float, heads: int,
                    rank: int, block_pos: int, precision):
    *page_refs, o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(0)
    start, n_real = pos_ref[0], pos_ref[1]
    tq = q_ref.shape[0]

    def visible(k):
        # row r's position start + r // heads sees key k <= it, which is
        # r >= (k - start) * heads; padding rows see what the last real row
        # sees (``seen``)
        r = qi * tq + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        return r >= (k - start) * heads

    walk(q_ref, page_refs, o_ref, m_scr, l_scr, acc_scr,
         seen=_last_seen(qi, start, n_real, block_pos), visible=visible,
         scale=scale, rank=rank, precision=precision)


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "scale", "block_rows", "pages_per_step", "interpret"))
def mla_prefill(q: jax.Array, pages: jax.Array, block_table: jax.Array,
                start: jax.Array, n_real: jax.Array, *, heads: int,
                rank: int, scale: float, block_rows: int = _BLOCK_ROWS,
                pages_per_step: int = _PAGES_PER_STEP,
                interpret: bool = False) -> jax.Array:
    """One sequence's chunk over its cached latent rows -> ``o`` ``[C*H,
    rank]`` in the dtype of ``q``, normalised.

    q ``[C*H, rank + rope]`` (row ``n*H + h``: position ``start + n``, head
    ``h``, ``Wkvb_k`` absorbed); pages ``[n_pages, page, rank + rope]``
    (the flat pool, ``kv_cache.flat_pool``); block_table ``[n_max]`` int32
    page ids (``kv_cache.block_pages``: this block's pages); ``start``,
    ``n_real`` the chunk's first position and its real (unpadded) length,
    whose rows the step has written to the pages before this call."""
    rows, width = q.shape
    n_max = block_table.shape[0]
    page = pages.shape[1]
    block_pos = _block_pos(rows // heads, heads, block_rows)
    tq = block_pos * heads
    g = max(1, min(pages_per_step, n_max))
    n_steps = -(-n_max // g)
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else None)
    kernel = functools.partial(
        _prefill_kernel, scale=float(scale), heads=heads, rank=rank,
        block_pos=block_pos, precision=precision)

    def page_spec(i):
        def index(qi, j, bt, pos):
            last = jnp.clip(_last_seen(qi, pos[0], pos[1], block_pos) // page,
                            0, n_max - 1)
            return bt[page_index(j, i, g, last)], 0, 0
        return pl.BlockSpec((1, page, width), index)

    rows_spec = lambda w: pl.BlockSpec((tq, w), lambda qi, j, bt, pos: (qi, 0))
    return pl.pallas_call(
        kernel,
        name="hvd_mla_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tq, n_steps),
            in_specs=[rows_spec(width)] + [page_spec(i) for i in range(g)],
            out_specs=rows_spec(rank),
            scratch_shapes=[
                pltpu.VMEM((tq, 1), jnp.float32),        # m
                pltpu.VMEM((tq, 1), jnp.float32),        # l
                pltpu.VMEM((tq, rank), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, rank), q.dtype),
        interpret=interpret,
        **compiler_kwargs(interpret),
    )(block_table.astype(jnp.int32),
      jnp.stack([jnp.asarray(start, jnp.int32),
                 jnp.asarray(n_real, jnp.int32)]),
      q, *([pages] * g))


def supports(dtype, rank: int, interpret: bool = False) -> bool:
    """Static gate of kernel dispatch: one native dtype (float32 or
    bfloat16) and, compiled for the chip, a latent ``rank`` of whole
    128-lane tiles (its slice of a cached row is then aligned)."""
    return (jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)
            and (interpret or rank % 128 == 0))
