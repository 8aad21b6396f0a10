"""Pallas TPU flash-attention kernel with streaming-softmax stats.

The hot op of the flagship transformer and of sequence parallelism. The
jnp fallback (``parallel/sequence._block_attend``) materializes a full
``[B, H, Sq, Sk]`` score matrix in HBM per ring step; this kernel keeps
score tiles in VMEM, streaming K/V blocks through a pipelined grid
dimension with the numerically-stable flash recurrence, so HBM traffic is
O(Sq·D + Sk·D) instead of O(Sq·Sk) — and causally-dead K blocks are
skipped entirely (≈2x on causal attention).

Contract (identical to ``_block_attend``, so it drops into ring/local
attention including the cross-shard merge): returns UNNORMALIZED
``o = exp(s - m) @ v`` plus per-row stats ``m`` (running max) and ``l``
(running sum), letting the caller merge partials across ring steps.
Kernel structure follows the upstream pallas flash kernel
(jax.experimental.pallas.ops.tpu.flash_attention): grid
``(B·H, n_q, n_k)`` with VMEM scratch carrying (m, l, acc) across the
``n_k`` (arbitrary-order) dimension, stats outputs padded to the 128-lane
minimum block.

Offsets ``q_offset``/``k_offset`` position the local blocks in the global
sequence for causal masking; they are traced scalars (ring step index ×
shard length), shipped to the kernel through SMEM — this is what the
upstream kernel lacks and ring attention needs.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SMEM = pltpu.SMEM

NEG_INF = -1e30
_LANES = 128     # TPU lane width: min last-dim block size


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
    aligned divisor of the actual lengths."""
    dbq, dbk = default_blocks()
    return (_fit_block(s_q, block_q or dbq, 8),
            _fit_block(s_k, block_k or dbk, _LANES))


def default_blocks() -> Tuple[int, int]:
    """(block_q, block_k) from the knobs. Measured on v5e (PERF.md r5):
    512/1024 cut the flagship TransformerLM step from 348 ms to 209 ms
    (+67% tok/s) vs the original 128/256 — per-grid-step overhead
    dominates at small blocks; the min()-clamp in the entry points keeps
    short sequences valid."""
    try:
        from horovod_tpu.config import knobs
        return (int(knobs.get("HOROVOD_FLASH_BLOCK_Q")),
                int(knobs.get("HOROVOD_FLASH_BLOCK_K")))
    except (ImportError, KeyError):  # pragma: no cover - config absent
        # Parse errors in user-set values must SURFACE, not silently
        # fall back — only a missing config module uses the defaults.
        return 512, 1024


def _kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            m_scr, l_scr, acc_scr, *, causal: bool, scale: float):
    blk_q, d = q_ref.shape[1], q_ref.shape[2]
    blk_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_start = qoff_ref[0] + qi * blk_q        # global positions (traced)
    k_start = koff_ref[0] + kb * blk_k
    # Causal block skip: the whole K block is in the future of every Q row
    # iff q_start + blk_q - 1 < k_start (ref: below_or_on_diag in the
    # upstream kernel, generalized to cross-shard offsets).
    should_run = (q_start + blk_q - 1 >= k_start) if causal else True

    @pl.when(should_run)
    def _run():
        # Tiles arrive in the model's native dtype (bf16 HBM traffic, bf16
        # MXU fast path for q.kT); only f32-accumulated intermediates are
        # cast, in VMEM.
        q = q_ref[0]                           # [blk_q, D] native dtype
        k = k_ref[0]                           # [blk_k, D] native dtype
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[...]                    # [blk_q, LANES]
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=1)[:, None]   # [blk_q, 1]
        m_next = jnp.maximum(m_prev, m_curr)   # [blk_q, LANES]
        reps = blk_k // _LANES
        p = jnp.exp(s - jnp.tile(m_next, (1, reps)))
        # Fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would attend
        # uniformly; zero them (same guard as the jnp fallback).
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_next))
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next

        v = v_ref[0].astype(jnp.float32)       # [blk_k, D]
        d_reps = max(d // _LANES, 1)
        a_scale = (jnp.tile(alpha, (1, d_reps)) if d >= _LANES
                   else alpha[:, :d])
        acc_scr[...] = acc_scr[...] * a_scale + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _finalize():
        o_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def flash_block_attend(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_offset, k_offset,
    causal: bool, scale: float,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash form of ``_block_attend``: q/k/v ``[B, S, H, D]`` →
    (o ``[B, Sq, H, D]`` unnormalized, m ``[B, H, Sq]``, l ``[B, H, Sq]``).
    Shapes must divide the block sizes (``supports()`` gates dispatch)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _resolve_blocks(s_q, s_k, block_q, block_k)
    if block_q is None or block_k is None:
        raise ValueError(
            f"flash kernel cannot block shapes Sq={s_q}, Sk={s_k} "
            f"(gate dispatch with supports())")
    # [B, S, H, D] -> [B*H, S, D], native dtype: the layout change is one
    # pass; no f32 upcast copies in HBM (casting happens per-tile in VMEM).
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)

    grid = (b * h, s_q // block_q, s_k // block_k)
    kernel = functools.partial(_kernel, causal=causal, scale=float(scale))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    o, m, l = pl.pallas_call(
        kernel,
        name="hvd_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=_SMEM),
            pl.BlockSpec(memory_space=_SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kb: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_q, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_q, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),        # acc
        ],
        interpret=interpret,
        **kwargs,
    )(qoff, koff, qf, kf, vf)

    o = o.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)     # [B, Sq, H, D]
    m = m[:, :, 0].reshape(b, h, s_q)
    l = l[:, :, 0].reshape(b, h, s_q)
    return o, m, l


# ---------------------------------------------------------------------------
# Differentiable full attention (custom VJP with pallas backward kernels).
#
# The block-level API above is forward-only (pallas_call has no automatic
# AD); training paths use `flash_attention`, whose backward pass runs two
# pallas kernels implementing the standard flash-attention gradients:
#   P_ij  = exp(S_ij - L_i)          (L = rowwise logsumexp, saved fwd)
#   dv_j  = sum_i P_ij do_i
#   dS_ij = P_ij (do_i . v_j - D_i)  (D = rowsum(do * o), computed outside)
#   dq_i  = scale * sum_j dS_ij k_j
#   dk_j  = scale * sum_i dS_ij q_i
# Each backward kernel recomputes its S tile in VMEM — no O(Sq*Sk) HBM
# residuals, same causal block-skip as the forward.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, l_ref,
                   d_ref, dq_ref, dq_scr, *, causal: bool, scale: float):
    blk_q, d = q_ref.shape[1], q_ref.shape[2]
    blk_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    q_start = qoff_ref[0] + qi * blk_q
    k_start = koff_ref[0] + kb * blk_k
    should_run = (q_start + blk_q - 1 >= k_start) if causal else True

    @pl.when(should_run)
    def _run():
        q = q_ref[0]                  # native dtype (bf16 MXU fast path)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        reps = blk_k // _LANES
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - jnp.tile(l_ref[0], (1, reps)))
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [blk_q, blk_k]
        ds = p * (dp - jnp.tile(d_ref[0], (1, reps)))
        dq_scr[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...] * scale


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, l_ref,
                    d_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, causal: bool, scale: float):
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    q_start = qoff_ref[0] + qi * blk_q
    k_start = koff_ref[0] + kb * blk_k
    should_run = (q_start + blk_q - 1 >= k_start) if causal else True

    @pl.when(should_run)
    def _run():
        q = q_ref[0]                  # native dtype (bf16 MXU fast path)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        reps = blk_k // _LANES
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - jnp.tile(l_ref[0], (1, reps)))
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            p = jnp.where(rows >= cols, p, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [blk_k, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.tile(d_ref[0], (1, reps)))
        dk_scr[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [blk_k, D]

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...] * scale
        dv_ref[0] = dv_scr[...]


def _lane_pad(x: jax.Array) -> jax.Array:
    """[BH, S] row stats -> [BH, S, LANES] broadcast for lane-aligned
    pallas input blocks."""
    return jnp.broadcast_to(x[:, :, None], x.shape + (_LANES,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=None, block_k=None, interpret=False):
    """Differentiable normalized flash attention, full-sequence case
    (q/k/v ``[B, S, H, D]`` -> ``[B, S, H, D]``). The training-path entry:
    forward = flash kernel, backward = pallas dq/dkv kernels."""
    out, _ = _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k,
                                  interpret)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k,
                         interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    o_un, m, l = flash_block_attend(q, k, v, 0, 0, causal=causal,
                                    scale=float(scale), block_q=block_q,
                                    block_k=block_k, interpret=interpret)
    l_safe = jnp.maximum(l, 1e-30)
    o = (o_un / jnp.moveaxis(l_safe, 1, -1)[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)                    # [B, H, S]
    return o, (q, k, v, o, lse)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
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
    Returns (dq [B,Sq,H,D], dk [B,Sk,H,D], dv [B,Sk,H,D]) in f32."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _resolve_blocks(s_q, s_k, block_q, block_k)
    if block_q is None or block_k is None:
        raise ValueError(
            f"flash backward cannot block shapes Sq={s_q}, Sk={s_k} "
            f"(gate dispatch with supports())")

    # Native dtype into the kernels (see fwd); casts happen per-tile.
    do = do.astype(q.dtype)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    dof = do.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    lsef = lse.astype(jnp.float32).reshape(b * h, s_q)
    dDf = dD.astype(jnp.float32).reshape(b * h, s_q)
    l_pad = _lane_pad(lsef)
    d_pad = _lane_pad(dDf)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal,
                          scale=float(scale)),
        name="hvd_flash_bwd_dq",
        grid=(b * h, s_q // block_q, s_k // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=_SMEM),
            pl.BlockSpec(memory_space=_SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kb: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, kb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(qoff, koff, qf, kf, vf, dof, l_pad, d_pad)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          scale=float(scale)),
        name="hvd_flash_bwd_dkv",
        grid=(b * h, s_k // block_k, s_q // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=_SMEM),
            pl.BlockSpec(memory_space=_SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, kb, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, kb, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, kb, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, kb, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_k, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(qoff, koff, qf, kf, vf, dof, l_pad, d_pad)

    unflat = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unflat(dq, s_q), unflat(dk, s_k), unflat(dv, s_k)


def _flash_attention_bwd(causal, scale, block_q, block_k, interpret,
                         res, do):
    q, k, v, o, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    dD = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1).transpose(0, 2, 1)            # [B, H, Sq]
    dq, dk, dv = flash_bwd_block(
        q, k, v, do, lse, dD, 0, 0, causal=causal, scale=float(scale),
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


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


