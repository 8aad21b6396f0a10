"""Sequence / context parallelism: ring attention and Ulysses (all-to-all).

The reference has NO sequence parallelism (SURVEY §5 "long-context ... Absent")
— only the primitives such schemes are built from (reducescatter, allgather,
alltoall with uneven splits, and P2P inside Adasum). This module supplies the
schemes themselves, TPU-native:

- ``ring_attention``: Q stays put; K/V blocks rotate around the ``sp`` mesh
  axis via ``lax.ppermute`` (ICI neighbour exchange), with blockwise-softmax
  (flash-style running max/sum) accumulation so the full S x S score matrix is
  never materialised. Compute on block i overlaps the transfer of block i+1 —
  XLA schedules the ppermute DMA concurrently with the matmuls.
- ``ulysses_attention``: all-to-all re-shard [S/sp, H] -> [S, H/sp] so each
  chip sees the full sequence for a head subset, runs plain attention, and
  re-shards back — exactly the alltoall pattern the reference exposes as a
  primitive (EnqueueTensorAlltoall operations.cc:1881).

Both are differentiable (pure lax), jit/scan-friendly (static shapes), and
compose with DP/TP axes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _static_scale(scale) -> Optional[float]:
    """float(scale) when concrete, None when traced — the single probe
    deciding kernel (static-scale) vs jnp dispatch everywhere."""
    try:
        return float(scale)
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        return None


def _block_attend(q, k, v, q_offset, k_offset, causal, scale):
    """One Q-block x K-block partial attention.

    Returns (unnormalised out, running logsumexp pieces): o = exp(s - m) @ v,
    m = rowmax(s), l = rowsum(exp(s - m)). Shapes: q [B, Sq, H, D],
    k/v [B, Sk, H, D] -> o [B, Sq, H, D], m/l [B, Sq, H].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qi = q_offset + lax.broadcasted_iota(jnp.int32, (q.shape[1], k.shape[1]), 0)
        ki = k_offset + lax.broadcasted_iota(jnp.int32, (q.shape[1], k.shape[1]), 1)
        s = jnp.where(qi[None, None] >= ki[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # Fully-masked rows (all NEG_INF, m == NEG_INF) must contribute nothing —
    # without this, exp(NEG_INF - NEG_INF) = 1 would attend uniformly.
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o, m, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise ring attention over a sequence-sharded axis.

    Args: q/k/v ``[B, S_local, H, D]`` — the local sequence shard, in ring
    order (chip i holds tokens [i*S_local, (i+1)*S_local)). Must be called
    inside shard_map/pmap with ``axis_name`` bound. Returns the attention
    output for the local Q shard, ``[B, S_local, H, D]``.

    Algorithm: each of the ``n`` steps attends Q_local against the currently
    held K/V block, accumulating with the numerically stable streaming-softmax
    merge, then rotates K/V one hop (ppermute ring). Computation at step t
    overlaps the DMA for step t+1 on ICI.

    Differentiation is a ring-level custom VJP: the backward pass is a
    second ring in which each chip differentiates its Q shard against the
    rotating K/V blocks (pallas ``flash_bwd_block`` kernels when eligible,
    jnp otherwise), accumulating dK/dV *on* the rotating blocks so each
    block arrives home with contributions from every chip. Forward blocks
    likewise dispatch to the pallas flash kernel when eligible."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scale_static = _static_scale(scale)   # custom-VJP needs a static scale
    if scale_static is None:
        return _ring_attention_plain(q, k, v, axis_name, causal, scale)
    return _ring_attention_cvjp(q, k, v, axis_name, causal, scale_static)


def _ring_flash_mode(q, k, v, scale):
    """(use_flash, interpret) trace-time dispatch decision. A traced
    (non-static) scale cannot reach the kernel — jnp path."""
    from horovod_tpu.ops.pallas import flash_attention as fa
    if _static_scale(scale) is None:
        return False, False
    mode = fa.enabled()
    if mode is None or not fa.supports(q, k, v):
        return False, False
    return True, mode == "interpret"


def _ring_fwd_scan(q, k, v, axis_name, causal, scale):
    """The forward ring; returns (out [B,Sq,H,D] in q.dtype,
    lse [B,H,Sq] f32 — the global logsumexp needed by the backward)."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = q.shape[1]
    use_flash, interpret = _ring_flash_mode(q, k, v, scale)

    acc0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block(kt, vt, ko):
        if use_flash:
            from horovod_tpu.ops.pallas import flash_attention as fa
            return fa.flash_block_attend(
                q, kt, vt, my * s_local, ko, causal=causal,
                scale=float(scale), interpret=interpret)
        return _block_attend(
            q.astype(jnp.float32), kt.astype(jnp.float32),
            vt.astype(jnp.float32),
            q_offset=my * s_local, k_offset=ko, causal=causal, scale=scale)

    def step(carry, t):
        acc, m, l, kt, vt = carry
        src = (my - t) % n  # which chip's block we currently hold
        o_blk, m_blk, l_blk = block(kt, vt, src * s_local)
        # streaming-softmax merge (m/l are [B, Sq, H]; block stats come
        # back [B, H, Sq])
        m_blk = jnp.moveaxis(m_blk, 1, -1)  # [B,H,Sq] -> [B,Sq,H]
        l_blk = jnp.moveaxis(l_blk, 1, -1)
        m_new = jnp.maximum(m, m_blk)
        # exp(-inf - -inf) guards: where both -inf keep 0 contribution
        c_old = jnp.where(jnp.isinf(m) | (m <= NEG_INF / 2), 0.0,
                          jnp.exp(m - m_new))
        c_blk = jnp.where(jnp.isinf(m_blk) | (m_blk <= NEG_INF / 2), 0.0,
                          jnp.exp(m_blk - m_new))
        acc = (acc * c_old[..., None]
               + o_blk.astype(jnp.float32) * c_blk[..., None])
        l = l * c_old + l_blk * c_blk
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        return (acc, m_new, l, kt, vt), None

    (acc, m, l, _, _), _ = lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = jnp.moveaxis(m + jnp.log(l_safe), -1, 1)       # [B, H, Sq]
    return out, lse


def _ring_attention_plain(q, k, v, axis_name, causal, scale):
    """Non-custom-VJP form (traced scale): differentiates through the
    scan/merge directly."""
    out, _ = _ring_fwd_scan(q, k, v, axis_name, causal, scale)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention_cvjp(q, k, v, axis_name, causal, scale):
    out, _ = _ring_attention_cvjp_fwd(q, k, v, axis_name, causal, scale)
    return out


def _ring_attention_cvjp_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_fwd_scan(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _bwd_block_jnp(q, k, v, do, lse, dD, qoff, koff, causal, scale):
    """jnp form of flash_bwd_block (the behavioral spec): gradients of one
    K/V block against global stats lse/dD [B,H,Sq]."""
    q32, k32, v32, do32 = (x.astype(jnp.float32) for x in (q, k, v, do))
    s = jnp.einsum("bqhd,bkhd->bhqk", q32, k32) * scale
    p = jnp.exp(s - lse[..., None])
    if causal:
        rows = qoff + lax.broadcasted_iota(
            jnp.int32, (q.shape[1], k.shape[1]), 0)
        cols = koff + lax.broadcasted_iota(
            jnp.int32, (q.shape[1], k.shape[1]), 1)
        p = jnp.where((rows >= cols)[None, None], p, 0.0)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do32, v32)
    ds = p * (dp - dD[..., None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k32) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q32) * scale
    return dq, dk, dv


def _ring_attention_cvjp_bwd(axis_name, causal, scale, res, dout):
    q, k, v, o, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = q.shape[1]
    use_flash, interpret = _ring_flash_mode(q, k, v, scale)
    dD = jnp.sum(dout.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1).transpose(0, 2, 1)             # [B, H, Sq]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def bwd_block(kt, vt, ko):
        if use_flash:
            from horovod_tpu.ops.pallas import flash_attention as fa
            return fa.flash_bwd_block(
                q, kt, vt, dout, lse, dD, my * s_local, ko,
                causal=causal, scale=float(scale), interpret=interpret)
        return _bwd_block_jnp(q, kt, vt, dout, lse, dD,
                              my * s_local, ko, causal, scale)

    def step(carry, t):
        dq, kt, vt, dkt, dvt = carry
        src = (my - t) % n
        dq_b, dk_b, dv_b = bwd_block(kt, vt, src * s_local)
        # dK/dV accumulate ON the rotating block: block j visits every
        # chip exactly once over n steps and arrives home fully summed.
        dq = dq + dq_b
        dkt = dkt + dk_b
        dvt = dvt + dv_b
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        dkt = lax.ppermute(dkt, axis_name, perm)
        dvt = lax.ppermute(dvt, axis_name, perm)
        return (dq, kt, vt, dkt, dvt), None

    zeros_q = jnp.zeros(q.shape, jnp.float32)
    zeros_k = jnp.zeros(k.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (zeros_q, k, v, zeros_k, jnp.zeros(v.shape, jnp.float32)),
        jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention_cvjp.defvjp(_ring_attention_cvjp_fwd,
                            _ring_attention_cvjp_bwd)


def local_attention(q, k, v, causal=True, scale=None):
    """Plain (single-shard) full attention — the sp-disabled path and the
    post-all-to-all step of Ulysses. Dispatches to the differentiable
    pallas flash kernel (ops/pallas/flash_attention.flash_attention:
    custom-VJP forward + dq/dkv backward kernels) on TPU; jnp blockwise
    fallback elsewhere."""
    from horovod_tpu.ops.pallas import flash_attention as fa
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    mode = fa.enabled()
    scale_static = _static_scale(scale)   # traced scale -> jnp path
    if mode is not None and scale_static is not None \
            and fa.supports(q, k, v):
        return fa.flash_attention(
            q, k, v, causal, scale_static,
            interpret=(mode == "interpret")).astype(q.dtype)
    o, m, l = _block_attend(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), 0, 0, causal, scale)
    del m
    l = jnp.moveaxis(l, 1, -1)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """DeepSpeed-Ulysses-style SP: all-to-all from sequence-sharded
    [B, S/n, H, D] to head-sharded [B, S, H/n, D], full-sequence attention on
    the local heads, all-to-all back. The axis size must divide the head
    count.
    """
    n = lax.axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"ulysses: heads {q.shape[2]} not divisible by {n}")

    def reshard_fwd(x):  # [B, S/n, H, D] -> [B, S, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def reshard_bwd(x):  # [B, S, H/n, D] -> [B, S/n, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qf, kf, vf = reshard_fwd(q), reshard_fwd(k), reshard_fwd(v)
    of = local_attention(qf, kf, vf, causal, scale)
    return reshard_bwd(of)


def sequence_shard(x: jax.Array, axis_name: str, seq_dim: int = 1):
    """Split a replicated [.., S, ..] array into this chip's sequence block —
    the entry reshard for SP regions (reducescatter/allgather pairs at region
    boundaries are the reference-primitive way, SURVEY §5; here a static
    slice since the input is replicated)."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    s = x.shape[seq_dim]
    if s % n != 0:
        raise ValueError(f"sequence length {s} not divisible by sp={n}")
    blk = s // n
    return lax.dynamic_slice_in_dim(x, i * blk, blk, axis=seq_dim)


def sequence_unshard(x: jax.Array, axis_name: str, seq_dim: int = 1):
    """Inverse of sequence_shard: all_gather the sequence blocks."""
    return lax.all_gather(x, axis_name, axis=seq_dim, tiled=True)
