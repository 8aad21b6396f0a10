"""Pallas TPU flash-attention kernel with streaming-softmax stats.

The hot op of the flagship transformer and of sequence parallelism. The
jnp fallback (``parallel/sequence._block_attend``) materializes a full
``[B, H, Sq, Sk]`` score matrix in HBM per ring step; this kernel keeps
score tiles in VMEM, streaming K/V blocks through a pipelined grid
dimension with the numerically-stable flash recurrence, so HBM traffic is
O(Sq·D + Sk·D) instead of O(Sq·Sk). A grid step's block is cut into
square compute tiles, and under the causal mask each tile is one of three
kinds, told from the traced offsets: wholly above the diagonal (skipped),
crossed by it (the body that builds the mask), wholly under it (the same
body with no ``iota``, compare or select); ``causal_block_census`` counts
them.

Contract. ONE tile step for every entry point: grid ``(B·H, n_q, n_k)``
with VMEM scratch carrying the running max ``m``, sum ``l`` and the
accumulator across the ``n_k`` (arbitrary-order) dimension, the statistics
as ``[block_q, 1]`` columns that broadcast in the arithmetic. Every product
takes its operands in THEIR dtype and accumulates in float32: the
probabilities are cast to the dtype of the tile they multiply (as
``_block_attend`` casts ``p.astype(v.dtype)``), so bfloat16 inputs run
bfloat16 products and float32 inputs float32 ones; ``exp``, ``m``, ``l``,
``lse`` and ``dD`` are float32 always. (On the chip the cast moves no bit:
at default precision the TPU compiler already rounds a float32 operand to
bfloat16 for the matrix unit, one pass, so float32 ``p`` and ``dS`` against
upcast tiles gave the same results and the same time, PERF.md §6 PR 36; the
cast states the contract and keeps float32 copies of the tiles out of
VMEM.) What a call writes when its last K
block is done depends on who merges, so there are two finalisations:

- ``flash_block_attend`` / ``flash_bwd_block`` (a partial block of ring
  attention, whose caller merges across ring steps): UNNORMALIZED
  ``o = exp(s - m) @ v`` in float32 plus the row statistics ``m`` and
  ``l``, identical to ``_block_attend``; gradients in float32 (the ring
  sums them over its steps).
- ``flash_attention`` (the whole softmax, the training path): ``o = acc /
  l`` in the input dtype and ONE statistic ``lse = m + log l``; its
  backward kernels write dq, dk, dv in the input dtype.

Row statistics travel between HBM and the kernels lane-dense, ``[B·H, 1,
S]`` (a ``[1, block_q]`` row a block): the kernels that need a column turn
the row in VMEM, the dk/dv kernel works on the transposed score tile
``k·qᵀ`` where the row broadcasts as it lies.

Offsets ``q_offset``/``k_offset`` position the local blocks in the global
sequence for causal masking; they are traced scalars (ring step index ×
shard length), shipped to the kernel through SMEM — this is what the
upstream kernel (jax.experimental.pallas.ops.tpu.flash_attention) lacks
and ring attention needs.
"""

from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SMEM = pltpu.SMEM

NEG_INF = -1e30
_LANES = 128     # TPU lane width: min last-dim block size
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b
# Edge of a compute tile inside a grid step's block (PR 36, kernels alone on
# v5e at [4, 2048, 16, 64] bfloat16): a tile is one straight-line body, and
# the forward's, which carries the softmax statistics from tile to tile, is
# fastest at 1024 (0.82 ms a layer; 1.17 at 512), the backward kernels',
# which carry nothing, at 512 (dq 0.87, dkv 1.04; 1.07 and 1.21 on whole
# 1024 blocks, 1.26 and 1.69 at 256): smaller tiles follow the diagonal
# more closely and pay more per tile.
_FWD_TILE = 1024
_BWD_TILE = 512


def _fit_block(s: int, cap: int, align: int):
    """Largest block <= min(cap, s) that divides s and is align-aligned;
    None if no aligned block exists. Keeps the kernel eligible for any
    sequence the old smaller defaults handled (a 768-row S fits a 384
    block, not the 512 default) instead of dropping to the full-scores
    jnp path."""
    for b in range(min(cap, s) // align * align, 0, -align):
        if s % b == 0:
            return b
    return None


def _resolve_blocks(s_q: int, s_k: int, block_q, block_k):
    """(block_q, block_k) for the given sequence lengths, or (None, None)
    if no aligned blocking exists — the ONE home of the resolution rule
    shared by the fwd/bwd entry points and supports(). Explicit arguments
    win; None picks the knob defaults; blocks shrink to the largest
    aligned divisor of the actual lengths. Both are lane-aligned (a
    statistic's ``[1, block_q]`` row is a block's last dimension), but a
    short Sq of whole sublanes may be one block."""
    dbq, dbk = default_blocks()
    cap_q = block_q or dbq
    whole_q = s_q if s_q <= cap_q and s_q % 8 == 0 else None
    return (_fit_block(s_q, cap_q, _LANES) or whole_q,
            _fit_block(s_k, block_k or dbk, _LANES))


def _tile_runs(q_start, tq, k_start):
    """A tile of ``tq`` queries from ``q_start`` has a visible pair under
    the causal mask: its last query sees its first key, at ``k_start``
    (ref: below_or_on_diag in the upstream kernel, generalized to
    cross-shard offsets)."""
    return q_start + tq - 1 >= k_start


def _tile_unmasked(q_start, k_start, tk):
    """Every pair of a tile is visible: its first query sees the last of
    its ``tk`` keys, so the tile needs no mask."""
    return q_start >= k_start + tk - 1


def _tile_edge(blk: int, tile: Optional[int]) -> int:
    """Edge of a compute tile along an axis of ``blk``: ``tile`` where it
    divides the block, else the whole block."""
    return tile if tile and blk % tile == 0 else blk


def causal_block_census(s_q: int, s_k: int, block_q: Optional[int] = None,
                        block_k: Optional[int] = None, q_offset: int = 0,
                        k_offset: int = 0, tile: Optional[int] = None
                        ) -> Dict[str, float]:
    """What the causal kernels do for one head, by the predicates the
    kernels use, counted in compute tiles (whole grid steps, or with
    ``tile`` the squares a kernel cuts its block into: ``_FWD_TILE``,
    ``_BWD_TILE``): ``skipped`` (wholly above the diagonal: no work),
    ``masked`` (the diagonal crosses them: the body with the mask) and
    ``unmasked`` (wholly under it: the body without), and
    ``pairs_computed_over_needed``, the query-key pairs of the tiles that
    run over the visible ones (1.0 where none is visible and none runs)."""
    block_q, block_k = _resolve_blocks(s_q, s_k, block_q, block_k)
    if block_q is None or block_k is None:
        raise ValueError(f"no aligned blocking of Sq={s_q}, Sk={s_k}")
    tq, tk = _tile_edge(block_q, tile), _tile_edge(block_k, tile)
    count = {"skipped": 0, "masked": 0, "unmasked": 0}
    for q_start in range(q_offset, q_offset + s_q, tq):
        for k_start in range(k_offset, k_offset + s_k, tk):
            if not _tile_runs(q_start, tq, k_start):
                count["skipped"] += 1
            elif _tile_unmasked(q_start, k_start, tk):
                count["unmasked"] += 1
            else:
                count["masked"] += 1
    needed = sum(min(max(q_offset + i - k_offset + 1, 0), s_k)
                 for i in range(s_q))
    computed = (count["masked"] + count["unmasked"]) * tq * tk
    return {**count, "pairs_computed_over_needed":
            computed / needed if needed else 1.0}


def default_blocks() -> Tuple[int, int]:
    """(block_q, block_k) from the knobs (their help text holds the chip
    measurements behind the defaults); the entry points shrink them to a
    divisor of short sequences."""
    try:
        from horovod_tpu.config import knobs
        return (int(knobs.get("HOROVOD_FLASH_BLOCK_Q")),
                int(knobs.get("HOROVOD_FLASH_BLOCK_K")))
    except (ImportError, KeyError):  # pragma: no cover - config absent
        # Parse errors in user-set values must SURFACE, not silently
        # fall back — only a missing config module uses the defaults.
        return 2048, 1024


def _causal_tiles(qoff_ref, koff_ref, qi, kb, blk_q: int, blk_k: int,
                  causal: bool, q_axis: int, step: Callable,
                  tile: Optional[int] = None) -> None:
    """Run ``step(visible, q_rows, k_rows)`` for every compute tile of grid
    step ``(qi, kb)`` (the whole block, or its ``tile``-edged squares;
    ``q_rows`` / ``k_rows`` are the tile's static slices of the block): not
    at all where no pair of the tile is visible, with ``visible=None``
    where every pair is, and with ``visible()`` -> the boolean tile of
    visible pairs (queries along ``q_axis``) only where the diagonal
    crosses it. Offsets are the traced global positions of the call's
    first query and key."""
    tq, tk = _tile_edge(blk_q, tile), _tile_edge(blk_k, tile)
    for r in range(blk_q // tq):
        for c in range(blk_k // tk):
            rows = slice(r * tq, (r + 1) * tq)
            cols = slice(c * tk, (c + 1) * tk)
            if not causal:
                step(None, rows, cols)
                continue
            q_start = qoff_ref[0] + qi * blk_q + r * tq
            k_start = koff_ref[0] + kb * blk_k + c * tk
            unmasked = _tile_unmasked(q_start, k_start, tk)

            def visible(q_start=q_start, k_start=k_start):
                shape = (tq, tk) if q_axis == 0 else (tk, tq)
                ahead = (
                    jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
                    - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
                return ahead >= k_start - q_start   # q position >= k position

            pl.when(unmasked)(
                functools.partial(step, None, rows, cols))
            pl.when(jnp.logical_and(_tile_runs(q_start, tq, k_start),
                                    jnp.logical_not(unmasked)))(
                functools.partial(step, visible, rows, cols))


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, *refs,
                causal: bool, scale: float, normalize: bool,
                tile: Optional[int]):
    """``normalize``: the whole-softmax finalisation (``o / l`` in the
    input dtype, ``lse``) against the partial block's (``o``, ``m``,
    ``l``); the tile step is the same."""
    outs, (m_scr, l_scr, acc_scr) = refs[:-3], refs[-3:]
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(visible, rows, cols):
        # Tiles arrive in the model's native dtype and go to the MXU as
        # they are; scores and statistics are float32.
        v = v_ref[0, cols, :]                          # [tile_k, D]
        s = jax.lax.dot_general(
            q_ref[0, rows, :], k_ref[0, cols, :], _NT,
            preferred_element_type=jnp.float32) * scale
        m_prev = m_scr[rows, :]                        # [tile_q, 1]
        if visible is None:
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            shift = m_next
        else:
            s = jnp.where(visible(), s, NEG_INF)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # A row that has seen no key yet has m = NEG_INF, and
            # exp(NEG_INF - NEG_INF) = 1 would attend uniformly: shift
            # such a row by 0 so that its masked scores give exp = 0
            # (the guard of the jnp fallback, on the column).
            shift = jnp.where(m_next <= NEG_INF / 2, 0.0, m_next)
        p = jnp.exp(s - shift)
        # first visible tile of a row: exp(NEG_INF - m) = 0 drops the
        # empty accumulator; a row still unseen keeps its zeros (1 * 0)
        alpha = jnp.exp(m_prev - m_next)
        l_scr[rows, :] = (l_scr[rows, :] * alpha
                          + jnp.sum(p, axis=1, keepdims=True))
        m_scr[rows, :] = m_next
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)

    _causal_tiles(qoff_ref, koff_ref, pl.program_id(1), kb, blk_q, blk_k,
                  causal, 0, step, tile)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        if normalize:
            o_ref, lse_ref = outs
            l = jnp.maximum(l_scr[...], 1e-30)   # a row with no key: o = 0
            o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
            lse_ref[0] = (m_scr[...] + jnp.log(l)).T
        else:
            o_ref, m_ref, l_ref = outs
            o_ref[0] = acc_scr[...]
            m_ref[0] = m_scr[...].T
            l_ref[0] = l_scr[...].T


def _heads_first(x: jax.Array) -> jax.Array:
    """[B, S, H, D] -> [B*H, S, D], native dtype: the layout change is one
    pass; no f32 upcast copies in HBM."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _blocks_or_raise(s_q: int, s_k: int, block_q, block_k, what: str):
    block_q, block_k = _resolve_blocks(s_q, s_k, block_q, block_k)
    if block_q is None or block_k is None:
        raise ValueError(
            f"flash {what} cannot block shapes Sq={s_q}, Sk={s_k} "
            f"(gate dispatch with supports())")
    return block_q, block_k


def _compiler_kwargs(interpret: bool) -> dict:
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                              "interpret", "normalize", "tile"))
def _attend(q, k, v, q_offset, k_offset, causal: bool, scale: float,
            block_q, block_k, interpret: bool, normalize: bool,
            tile: Optional[int] = _FWD_TILE):
    """The forward kernel over q/k/v ``[B, S, H, D]``: ``normalize`` ->
    (o in the input dtype, lse ``[B, H, Sq]``), else (o unnormalized
    float32, m, l)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _blocks_or_raise(s_q, s_k, block_q, block_k,
                                        "kernel")
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0))
    stat_spec = pl.BlockSpec((1, 1, block_q), lambda bh, qi, kb: (bh, 0, qi))
    stat = jax.ShapeDtypeStruct((b * h, 1, s_q), jnp.float32)
    n_stats = 1 if normalize else 2               # lse, or m and l
    o, *stats = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=float(scale),
                          normalize=normalize, tile=tile),
        name="hvd_flash_fwd",
        grid=(b * h, s_q // block_q, s_k // block_k),
        in_specs=[pl.BlockSpec(memory_space=_SMEM),
                  pl.BlockSpec(memory_space=_SMEM),
                  q_spec, kv_spec, kv_spec],
        out_specs=[q_spec] + [stat_spec] * n_stats,
        out_shape=[jax.ShapeDtypeStruct(
            (b * h, s_q, d), q.dtype if normalize else jnp.float32)]
        + [stat] * n_stats,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),        # m
            pltpu.VMEM((block_q, 1), jnp.float32),        # l
            pltpu.VMEM((block_q, d), jnp.float32),        # acc
        ],
        interpret=interpret,
        **_compiler_kwargs(interpret),
    )(qoff, koff, _heads_first(q), _heads_first(k), _heads_first(v))
    o = o.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)     # [B, Sq, H, D]
    return (o, *(x.reshape(b, h, s_q) for x in stats))


def flash_block_attend(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_offset, k_offset,
    causal: bool, scale: float,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash form of ``_block_attend``: q/k/v ``[B, S, H, D]`` →
    (o ``[B, Sq, H, D]`` unnormalized float32, m ``[B, H, Sq]``,
    l ``[B, H, Sq]``) for the caller to merge.
    Shapes must divide the block sizes (``supports()`` gates dispatch)."""
    return _attend(q, k, v, q_offset, k_offset, causal=causal,
                   scale=float(scale), block_q=block_q, block_k=block_k,
                   interpret=interpret, normalize=False)


# ---------------------------------------------------------------------------
# Differentiable full attention (custom VJP with pallas backward kernels).
#
# pallas_call has no automatic AD; training paths use `flash_attention`,
# whose backward pass runs two pallas kernels implementing the standard
# flash-attention gradients:
#   P_ij  = exp(S_ij - L_i)          (L = rowwise logsumexp, saved fwd)
#   dv_j  = sum_i P_ij do_i
#   dS_ij = P_ij (do_i . v_j - D_i)  (D = rowsum(do * o), computed outside)
#   dq_i  = scale * sum_j dS_ij k_j
#   dk_j  = scale * sum_i dS_ij q_i
# Each backward kernel recomputes its S tile in VMEM — no O(Sq*Sk) HBM
# residuals, the same three kinds of tile as the forward. The dq
# kernel sums over K blocks with L and D as columns; the dk/dv kernel sums
# over Q blocks on the TRANSPOSED tile S^T = k q^T, where L and D broadcast
# as the rows they arrive as and both sums are plain products (no
# transposed operand).
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   dd_ref, dq_ref, dq_scr, lse_scr, dd_scr,
                   *, causal: bool, scale: float, tile: Optional[int]):
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        lse_scr[...] = lse_ref[0].T           # [1, blk_q] -> [blk_q, 1]
        dd_scr[...] = dd_ref[0].T

    def step(visible, rows, cols):
        k = k_ref[0, cols, :]
        s = jax.lax.dot_general(
            q_ref[0, rows, :], k, _NT,
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_scr[rows, :])
        if visible is not None:
            p = jnp.where(visible(), p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0, rows, :], v_ref[0, cols, :], _NT,
            preferred_element_type=jnp.float32)       # [tile_q, tile_k]
        ds = p * (dp - dd_scr[rows, :])
        dq_scr[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _causal_tiles(qoff_ref, koff_ref, pl.program_id(1), kb, blk_q, blk_k,
                  causal, 0, step, tile)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    dd_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, causal: bool, scale: float, tile: Optional[int]):
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def step(visible, rows, cols):
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        st = jax.lax.dot_general(
            k_ref[0, cols, :], q, _NT,
            preferred_element_type=jnp.float32) * scale   # [tile_k, tile_q]
        pt = jnp.exp(st - lse_ref[0, :, rows])            # row [1, tile_q]
        if visible is not None:
            pt = jnp.where(visible(), pt, 0.0)
        dv_scr[cols, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)           # [tile_k, D]
        dpt = jax.lax.dot_general(
            v_ref[0, cols, :], do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - dd_ref[0, :, rows])
        dk_scr[cols, :] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)           # [tile_k, D]

    _causal_tiles(qoff_ref, koff_ref, qi, pl.program_id(1), blk_q, blk_k,
                  causal, 1, step, tile)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=None, block_k=None, interpret=False):
    """Differentiable normalized flash attention, full-sequence case
    (q/k/v ``[B, S, H, D]`` -> ``[B, S, H, D]`` in the input dtype). The
    training-path entry: forward = flash kernel, backward = pallas dq/dkv
    kernels, every result written by its kernel in its final form."""
    out, _ = _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k,
                                  interpret)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k,
                         interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    o, lse = _attend(q, k, v, 0, 0, causal=causal, scale=float(scale),
                     block_q=block_q, block_k=block_k, interpret=interpret,
                     normalize=True)
    return o, (q, k, v, o, lse)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                              "interpret", "grad_dtype", "tile"))
def _attend_bwd(q, k, v, do, lse, dD, q_offset, k_offset, causal: bool,
                scale: float, block_q, block_k, interpret: bool, grad_dtype,
                tile: Optional[int] = _BWD_TILE):
    """The two backward kernels; dq, dk, dv ``[B, S, H, D]`` written in
    ``grad_dtype``."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _blocks_or_raise(s_q, s_k, block_q, block_k,
                                        "backward")
    # Native dtype into the kernels (see fwd); statistics as rows.
    operands = (jnp.asarray(q_offset, jnp.int32).reshape(1),
                jnp.asarray(k_offset, jnp.int32).reshape(1),
                _heads_first(q), _heads_first(k), _heads_first(v),
                _heads_first(do.astype(q.dtype)),
                lse.astype(jnp.float32).reshape(b * h, 1, s_q),
                dD.astype(jnp.float32).reshape(b * h, 1, s_q))
    smem = pl.BlockSpec(memory_space=_SMEM)

    def specs(q_index, k_index, stat_index):
        q_spec = pl.BlockSpec((1, block_q, d), q_index)
        kv_spec = pl.BlockSpec((1, block_k, d), k_index)
        stat_spec = pl.BlockSpec((1, 1, block_q), stat_index)
        return q_spec, kv_spec, [smem, smem, q_spec, kv_spec, kv_spec,
                                 q_spec, stat_spec, stat_spec]

    q_spec, _, in_specs = specs(lambda bh, qi, kb: (bh, qi, 0),
                                lambda bh, qi, kb: (bh, kb, 0),
                                lambda bh, qi, kb: (bh, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal,
                          scale=float(scale), tile=tile),
        name="hvd_flash_bwd_dq",
        grid=(b * h, s_q // block_q, s_k // block_k),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), grad_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),   # lse
                        pltpu.VMEM((block_q, 1), jnp.float32)],  # dD
        interpret=interpret,
        **_compiler_kwargs(interpret),
    )(*operands)

    _, kv_spec, in_specs = specs(lambda bh, kb, qi: (bh, qi, 0),
                                 lambda bh, kb, qi: (bh, kb, 0),
                                 lambda bh, kb, qi: (bh, 0, qi))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          scale=float(scale), tile=tile),
        name="hvd_flash_bwd_dkv",
        grid=(b * h, s_k // block_k, s_q // block_q),
        in_specs=in_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, s_k, d), grad_dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        **_compiler_kwargs(interpret),
    )(*operands)

    unflat = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unflat(dq, s_q), unflat(dk, s_k), unflat(dv, s_k)


def flash_bwd_block(q, k, v, do, lse, dD, q_offset, k_offset,
                    causal: bool, scale: float,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Block-level flash backward with global positioning: gradients of
    normalized attention against the GLOBAL softmax stats ``lse`` (rowwise
    logsumexp over the full sequence) and ``dD`` (rowsum(do*o)), both
    ``[B, H, Sq]``. Offsets are traced scalars, as in the forward —
    this is the building block of the ring-attention backward pass
    (each ring step differentiates its K/V block in place).
    Returns (dq [B,Sq,H,D], dk [B,Sk,H,D], dv [B,Sk,H,D]) in f32, for the
    ring to sum over its steps."""
    return _attend_bwd(q, k, v, do, lse, dD, q_offset, k_offset,
                       causal=causal, scale=float(scale), block_q=block_q,
                       block_k=block_k, interpret=interpret,
                       grad_dtype=jnp.float32)


def _flash_attention_bwd(causal, scale, block_q, block_k, interpret,
                         res, do):
    q, k, v, o, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    dD = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1).transpose(0, 2, 1)            # [B, H, Sq]
    return _attend_bwd(q, k, v, do, lse, dD, 0, 0, causal=causal,
                       scale=float(scale), block_q=block_q, block_k=block_k,
                       interpret=interpret, grad_dtype=q.dtype)


flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


# ---------------------------------------------------------------------------
# Paged single-position decode attention (serving hot path).
#
# The serving engine (horovod_tpu/serving) keeps each sequence's K/V in
# fixed-size pages of a shared pool (PagedAttention, vLLM SOSP '23); at
# decode, every request contributes ONE query position that must attend over
# its pages in block-table order. The kernel below is the decode form of the
# flash kernel above: grid ``(B, n_max_pages)`` with the page dimension
# arbitrary-order, the flash (m, l, acc) recurrence in VMEM scratch, and the
# page -> physical-block indirection done by the BlockSpec index_map reading
# the scalar-prefetched block table (``pltpu.PrefetchScalarGridSpec``) — K/V
# pages stream straight from their pool slots, no gather materializes a
# contiguous copy in HBM.
#
# A page is ``[page, KVH*D]``: one row a token, every KV head's ``D`` numbers
# side by side on the lanes (``kv_cache.dense_rows``). A head of 64 fills its
# half of a 128-lane tile, its neighbour the other half, so a page in HBM and
# in VMEM is whole tiles and a grid step's DMA moves the keys' own bytes.
# One grid step holds a WHOLE page — every KV head — and takes the per-head
# sums over the heads' lane segments on the MXU: the queries arrive as ONE
# block-diagonal matrix ``[H, KVH*D]`` (row ``g*KVH + kv`` holds query head
# ``kv*qpk + g`` in KV head ``kv``'s lanes, zeros elsewhere), so
# ``Qbd . K^T`` ``[H, page]`` is every head's scores against its own KV head
# (products of the pool's dtype, accumulated in float32: for bfloat16 each
# product is exact) and ``P . V`` ``[H, KVH*D]`` holds head ``h``'s value sum
# in its KV head's lanes (the other lanes are other heads' values under this
# head's probabilities: never read). The probabilities stay float32: against
# a bfloat16 page they go through the MXU as three bfloat16 terms whose sum
# is the float32 number. Grouped queries (GQA) share the resident page.
# ---------------------------------------------------------------------------

def _weighted_rows(p: jax.Array, v: jax.Array) -> jax.Array:
    """``p @ v`` in float32 with ``p`` ``[H, page]`` float32 kept whole:
    against float32 rows one product at the highest precision; against
    bfloat16 rows ``p`` split into three bfloat16 terms (8 + 8 + 8 bits of
    its 24) stacked into one product, each term's products exact."""
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    h = p.shape[0]
    terms, rest = [], p
    for _ in range(3):
        t = rest.astype(v.dtype)
        terms.append(t)
        rest = rest - t.astype(jnp.float32)
    out = jax.lax.dot_general(
        jnp.concatenate(terms, axis=0), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out[:h] + out[h:2 * h] + out[2 * h:]


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float, page: int,
                         kvh: int):
    h, w = q_ref.shape[1], q_ref.shape[2]
    qpk, d = h // kvh, w // kvh
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    length = len_ref[b]
    base = j * page
    # Pages wholly past the sequence's length are skipped (the block-table
    # entries there point at the scratch page) — the decode analogue of the
    # causal block skip in the training kernel.
    @pl.when(base < length)
    def _run():
        k = k_ref[0]                                       # [page, KVH*D]
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            precision=(jax.lax.Precision.HIGHEST
                       if k.dtype == jnp.float32 else None),
            preferred_element_type=jnp.float32) * scale    # [H, page]
        live = base + jax.lax.broadcasted_iota(
            jnp.int32, (h, page), 1) < length
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_scr[...]                                # [H, 1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # base < length: at least one live key, so m_next is finite.
        p = jnp.where(live, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)       # first page: exp(-1e30) = 0
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = acc_scr[...] * alpha + _weighted_rows(p, v_ref[0])

    @pl.when(j == n_j - 1)
    def _finalize():
        # Decode output is normalized in-kernel: there is no cross-shard
        # stats merge at a single query position (unlike the training
        # kernel's ring-attention contract). A fully-masked row (an empty
        # slot, length 0) finalizes to exact zeros via the l floor.
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)  # [H, KVH*D]
        # row g*KVH + kv keeps KV head kv's lanes; the KVH rows of one g
        # then add up to one lane-dense row of qpk's output
        row = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
        o = jnp.where(lane // d == row % kvh, o, 0.0)
        for g in range(qpk):                   # static: query heads per KV
            o_ref[0, g] = jnp.sum(o[g * kvh:(g + 1) * kvh], axis=0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_paged_decode(
    q: jax.Array,                     # [B, H, D] one position per sequence
    k_pages: jax.Array,               # [n_pages, page, KVH*D]
    v_pages: jax.Array,
    block_tables: jax.Array,          # [B, n_max] i32 physical page ids
    lengths: jax.Array,               # [B] i32 valid tokens per sequence
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention -> normalized ``[B, H, D]`` f32 output.

    Shapes must pass :func:`paged_decode_supports`; the jnp fallback
    (``serving.kv_cache.paged_attention_reference``) covers the rest.
    """
    b, h, d = q.shape
    n_pages, page, w = k_pages.shape
    kvh = w // d
    n_max = block_tables.shape[1]
    qpk = h // kvh
    # Head h reads KV head h // qpk. The block-diagonal queries: row
    # g*KVH + kv is head kv*qpk + g on KV head kv's lanes of the page's row.
    qg = q.reshape(b, kvh, qpk, d).transpose(0, 2, 1, 3)   # [B, qpk, KVH, D]
    qbd = (qg[:, :, :, None, :]
           * jnp.eye(kvh, dtype=q.dtype)[:, :, None]).reshape(b, h, w)
    kernel = functools.partial(_paged_decode_kernel, scale=float(scale),
                               page=page, kvh=kvh)
    bt = block_tables.astype(jnp.int32)
    ln = lengths.astype(jnp.int32)
    page_spec = pl.BlockSpec(
        (1, page, w), lambda b_, j, bt_, ln_: (bt_[b_, j], 0, 0))
    slot_spec = lambda rows: pl.BlockSpec(
        (1, rows, w), lambda b_, j, bt_, ln_: (b_, 0, 0))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        name="hvd_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_max),
            in_specs=[slot_spec(h), page_spec, page_spec],
            out_specs=slot_spec(qpk),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),          # m
                pltpu.VMEM((h, 1), jnp.float32),          # l
                pltpu.VMEM((h, w), jnp.float32),          # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, qpk, w), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(bt, ln, qbd, k_pages, v_pages)
    return out.reshape(b, qpk, kvh, d).transpose(0, 2, 1, 3).reshape(b, h, d)


def paged_decode_supports(q: jax.Array, k_pages: jax.Array,
                          v_pages: Optional[jax.Array] = None) -> bool:
    """Static shape gate for paged-decode kernel dispatch (the decode
    analogue of :func:`supports`): one native dtype (float32 or bfloat16),
    K and V pools of one shape whose row is whole KV heads of the query's
    ``D``, and Q heads grouping evenly over them. Page size and head count
    are free — a block is a whole page, its trailing dimensions the
    pool's own."""
    if q.ndim != 3 or k_pages.ndim != 3:
        return False
    b, h, d = q.shape
    if v_pages is not None and (v_pages.shape != k_pages.shape
                                or v_pages.dtype != k_pages.dtype):
        return False
    if q.dtype != k_pages.dtype or q.dtype not in (jnp.float32,
                                                   jnp.bfloat16):
        return False
    kvh, rest = divmod(k_pages.shape[2], d)
    return rest == 0 and kvh > 0 and h % kvh == 0


def supports(q: jax.Array, k: jax.Array, v: Optional[jax.Array] = None,
             block_q: Optional[int] = None,
             block_k: Optional[int] = None) -> bool:
    """Static shape gate for kernel dispatch."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if v is not None and (v.shape != k.shape or v.dtype != k.dtype):
        return False      # kernel assumes d_v == d_qk and Sv == Sk
    if q.dtype != k.dtype:
        return False      # one native dtype through the kernel
    bq, bk = _resolve_blocks(s_q, s_k, block_q, block_k)
    return (bq is not None and bk is not None
            and (d % _LANES == 0 or d < _LANES))


def compiled_kernels(hlo_text: str) -> Dict[str, int]:
    """Mosaic-compiled instances of this module's kernels in a compiled
    executable's HLO text, by ``pallas_call`` name (``hvd_flash_fwd``,
    ``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``, ``hvd_paged_decode``).
    A kernel in interpret mode, or a step that took the jnp path, leaves
    no ``tpu_custom_call`` — so an empty dict is how a caller tells that
    the device path was not taken."""
    found: Dict[str, int] = {}
    for name in re.findall(
            r"%(hvd_[a-z_]+)[.\d]* = [^\n]*custom_call_target="
            r'"tpu_custom_call"', hlo_text):
        found[name] = found.get(name, 0) + 1
    return found


def enabled() -> Optional[object]:
    """Dispatch policy: True -> compiled kernel (TPU backend), 'interpret'
    off-TPU when HOROVOD_TPU_PALLAS=interpret asks for it (tests), None ->
    jnp path (the knob says 0, or there is no TPU to compile for)."""
    from horovod_tpu.config import knobs
    knob = str(knobs.get("HOROVOD_TPU_PALLAS"))
    if knob in ("0", "false", "False"):
        return None
    if jax.default_backend() == "tpu":
        return True
    if knob == "interpret":        # CPU correctness testing
        return "interpret"
    return None


