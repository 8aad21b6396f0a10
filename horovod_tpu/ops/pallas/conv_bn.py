"""Pallas TPU fused 1x1-conv + batch-norm kernel (stats epilogue,
normalize+ReLU prologue) — the attack on the BN-bandwidth bottleneck.

Motivation (PERF.md profile, ResNet-50 bf16 batch 256 on v5e): ~70 % of
step time is BN-related HBM traffic — separate XLA fusions re-read each
conv output for statistics and again for normalize, because XLA cannot
fuse a cross-row reduction into a convolution's epilogue. A 1x1
convolution in NHWC *is* a GEMM ``Y[M,Cout] = X[M,Cin] @ W[Cin,Cout]``
(M = N*H*W), so this kernel:

- computes the GEMM on the MXU with f32 accumulation,
- folds the *previous* BN's normalize + ReLU into the A-operand load
  (prologue: ``relu(x*inv + shift)`` — the normalized activation is never
  materialized in HBM), and
- accumulates per-channel ``sum`` / ``sum of squares`` of the (bf16-
  rounded) output in VMEM as the tiles stream out (epilogue: the BN
  statistics pass costs zero extra HBM traffic).

The backward pass is ONE kernel producing dX, dW, d_inv, d_shift in a
single streaming pass over (x, y, dy): the BN-backward correction
``dy_eff = dy + ds1 + 2*ds2*y`` and the prologue backward (ReLU mask,
per-channel reductions) are computed per-tile in VMEM, where the XLA
composition spends separate bandwidth-bound fusions on each.

Grid: ``(M/bm, N/bn)`` forward, ``(M/bm,)`` backward, both with
sequential ("arbitrary") semantics — stats/dW accumulate across grid
steps in VMEM-resident outputs, which requires a single core walking the
grid in order. W stays whole in VMEM (1x1 weights are <=2 MB); the A
tile is fetched once per m-step and reused across the n loop.

Reference framework has no analogue (its models use cuDNN's fused
BN-conv paths); role corresponds to the keep-the-accelerator-busy perf
story of docs/benchmarks.rst:13-43.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_bm_bwd(kp: int, np_: int, cap: int) -> int:
    """Largest backward m-block fitting the ~16 MB VMEM budget: double-
    buffered x/y/dy/dx streams + resident W (bf16) and dW (f32)."""
    for bm in (512, 256, 128, 64):
        if bm > cap:
            continue
        vmem = (2 * bm * kp * 2          # x in, double-buffered
                + 2 * 2 * bm * np_ * 2   # y, dy in
                + 2 * bm * kp * 2        # dx out
                + kp * np_ * 2           # W resident
                + kp * np_ * 4           # dW accumulator
                + bm * np_ * 4)          # dy_eff f32 intermediate
        if vmem <= 12 * 1024 * 1024:
            return bm
    return 64


# ---------------------------------------------------------------------------
# Forward kernel: Y = relu(X*inv + shift) @ W, s1 = sum(Y), s2 = sum(Y^2)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, inv_ref, shift_ref, y_ref, s1_ref, s2_ref,
                *scratch, prologue: bool, m_valid: Optional[int],
                bm: int, bn: int):
    m = pl.program_id(0)
    n = pl.program_id(1)
    if prologue:
        xh_scr, = scratch
        # The A tile is loaded once per m-step and reused across the whole
        # n loop; compute the normalized activation once into scratch.
        @pl.when(n == 0)
        def _():
            pre = (x_ref[...].astype(jnp.float32) * inv_ref[...]
                   + shift_ref[...])
            xh_scr[...] = jnp.maximum(pre, 0.0).astype(xh_scr.dtype)
        xh = xh_scr[...]
    else:
        xh = x_ref[...]
    off = pl.multiple_of(n * bn, bn)
    wblk = w_ref[:, pl.ds(off, bn)]
    y = jax.lax.dot_general(xh, wblk, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    yc = y.astype(y_ref.dtype)
    y_ref[...] = yc
    # Statistics of the STORED (dtype-rounded) values — the same tensor a
    # separate BN pass would have read back, so numerics match the
    # unfused composition.
    ys = yc.astype(jnp.float32)
    if m_valid is not None:
        rows = m * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        ys = jnp.where(rows < m_valid, ys, 0.0)
    c1 = jnp.sum(ys, axis=0)
    c2 = jnp.sum(ys * ys, axis=0)

    @pl.when(m == 0)
    def _():
        s1_ref[0, pl.ds(off, bn)] = c1
        s2_ref[0, pl.ds(off, bn)] = c2

    @pl.when(m > 0)
    def _():
        s1_ref[0, pl.ds(off, bn)] += c1
        s2_ref[0, pl.ds(off, bn)] += c2


# ---------------------------------------------------------------------------
# Backward kernel (one streaming pass):
#   dy_eff  = dy + ds1 + 2*ds2*y          (BN-stats backward correction)
#   g       = dy_eff @ W^T
#   dX      = g * relu'(pre) * inv        (prologue backward; g if none)
#   d_inv   = sum_m(g * relu'(pre) * x);  d_shift = sum_m(g * relu'(pre))
#   dW      = relu(pre)^T @ dy_eff
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, w_ref, inv_ref, shift_ref, y_ref, dy_ref,
                ds1_ref, ds2_ref, dx_ref, dw_ref, dinv_ref, dshift_ref,
                *, prologue: bool, m_valid: Optional[int], bm: int):
    m = pl.program_id(0)
    f32 = jnp.float32
    dyeff = (dy_ref[...].astype(f32) + ds1_ref[...]
             + 2.0 * ds2_ref[...] * y_ref[...].astype(f32))
    if m_valid is not None:
        rows = m * bm + jax.lax.broadcasted_iota(
            jnp.int32, dyeff.shape, 0)
        dyeff = jnp.where(rows < m_valid, dyeff, 0.0)
    dyc = dyeff.astype(x_ref.dtype)              # bf16 MXU fast path
    g = jax.lax.dot_general(dyc, w_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
    if prologue:
        x = x_ref[...].astype(f32)
        pre = x * inv_ref[...] + shift_ref[...]
        gm = jnp.where(pre > 0.0, g, 0.0)
        dx = gm * inv_ref[...]
        xh = jnp.maximum(pre, 0.0).astype(x_ref.dtype)
        dinv_c = jnp.sum(gm * x, axis=0)[None, :]
        dshift_c = jnp.sum(gm, axis=0)[None, :]
    else:
        dx = g
        xh = x_ref[...]
        dinv_c = jnp.zeros(dinv_ref.shape, f32)
        dshift_c = jnp.zeros(dshift_ref.shape, f32)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dwc = jax.lax.dot_general(xh, dyc, (((0,), (0,)), ((), ())),
                              preferred_element_type=f32)

    @pl.when(m == 0)
    def _():
        dw_ref[...] = dwc
        dinv_ref[...] = dinv_c
        dshift_ref[...] = dshift_c

    @pl.when(m > 0)
    def _():
        dw_ref[...] += dwc
        dinv_ref[...] += dinv_c
        dshift_ref[...] += dshift_c


# ---------------------------------------------------------------------------
# pallas_call plumbing (padded 2D operands; cfg is the static signature)
# ---------------------------------------------------------------------------

def _fwd_call(cfg, x, w, inv, shift):
    prologue, m_valid, bm, bn, _bmb, interpret = cfg
    mp, kp = x.shape
    np_ = w.shape[1]
    grid = (mp // bm, np_ // bn)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    scratch = [pltpu.VMEM((bm, kp), x.dtype)] if prologue else []
    kernel = functools.partial(
        _fwd_kernel, prologue=prologue, m_valid=m_valid, bm=bm, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda m, n: (m, 0)),
            pl.BlockSpec((kp, np_), lambda m, n: (0, 0)),
            pl.BlockSpec((1, kp), lambda m, n: (0, 0)),
            pl.BlockSpec((1, kp), lambda m, n: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
            pl.BlockSpec((1, np_), lambda m, n: (0, 0)),
            pl.BlockSpec((1, np_), lambda m, n: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), x.dtype),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(x, w, inv, shift)


def _bwd_call(cfg, x, w, inv, shift, y, dy, ds1, ds2):
    # The backward streams three (bm, N)/(bm, K) operands AND holds the
    # f32 dW accumulator + whole W resident — its VMEM budget is tighter
    # than the forward's, hence its own (smaller) block size.
    prologue, m_valid, _bmf, bn, bm, interpret = cfg
    mp, kp = x.shape
    np_ = w.shape[1]
    grid = (mp // bm,)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    kernel = functools.partial(
        _bwd_kernel, prologue=prologue, m_valid=m_valid, bm=bm)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda m: (m, 0)),
            pl.BlockSpec((kp, np_), lambda m: (0, 0)),
            pl.BlockSpec((1, kp), lambda m: (0, 0)),
            pl.BlockSpec((1, kp), lambda m: (0, 0)),
            pl.BlockSpec((bm, np_), lambda m: (m, 0)),
            pl.BlockSpec((bm, np_), lambda m: (m, 0)),
            pl.BlockSpec((1, np_), lambda m: (0, 0)),
            pl.BlockSpec((1, np_), lambda m: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, kp), lambda m: (m, 0)),
            pl.BlockSpec((kp, np_), lambda m: (0, 0)),
            pl.BlockSpec((1, kp), lambda m: (0, 0)),
            pl.BlockSpec((1, kp), lambda m: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kp), x.dtype),
            jax.ShapeDtypeStruct((kp, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(x, w, inv, shift, y, dy, ds1, ds2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_bn(cfg, x, w, inv, shift):
    return _fwd_call(cfg, x, w, inv, shift)


def _conv_bn_fwd(cfg, x, w, inv, shift):
    out = _fwd_call(cfg, x, w, inv, shift)
    return out, (x, w, inv, shift, out[0])


def _conv_bn_bwd(cfg, res, cts):
    x, w, inv, shift, y = res
    dy, ds1, ds2 = cts
    dx, dw, dinv, dshift = _bwd_call(cfg, x, w, inv, shift, y, dy, ds1, ds2)
    return dx, dw.astype(w.dtype), dinv, dshift


_conv_bn.defvjp(_conv_bn_fwd, _conv_bn_bwd)


# ---------------------------------------------------------------------------
# Public wrapper: NHWC / HWIO, stride subsampling, lane padding
# ---------------------------------------------------------------------------

def conv1x1_bn_stats(
    x: jax.Array, w: jax.Array,
    inv: Optional[jax.Array] = None, shift: Optional[jax.Array] = None,
    *, strides: Tuple[int, int] = (1, 1),
    block_m: int = 512, block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused ``y = conv1x1(relu(x*inv + shift), w)`` (NHWC) returning
    ``(y, sum(y), sum(y^2))`` with the per-channel sums taken over
    N*H*W of the dtype-rounded output. ``inv``/``shift`` of shape (Cin,)
    enable the normalize+ReLU prologue (pass None for a plain conv —
    e.g. the first conv of a block, whose input is already activated).
    Stride-2 1x1 convs subsample rows first (a 1x1 kernel never mixes
    spatial positions). Differentiable (single-pass Pallas backward)."""
    n, h, wdim, cin = x.shape
    if w.ndim == 4:                    # HWIO with 1x1 spatial
        assert w.shape[:2] == (1, 1), w.shape
        w = w.reshape(w.shape[2], w.shape[3])
    cout = w.shape[1]
    if strides != (1, 1):
        x = x[:, ::strides[0], ::strides[1], :]
        n, h, wdim = x.shape[0], x.shape[1], x.shape[2]
    m = n * h * wdim
    if block_m < _LANES or block_m & (block_m - 1):
        raise ValueError(f"block_m must be a power of two >= {_LANES} "
                         f"(got {block_m}): the backward block size is "
                         f"derived from it and both must divide the "
                         f"padded M")
    if block_n < _LANES or block_n % _LANES:
        raise ValueError(f"block_n must be a multiple of {_LANES} "
                         f"(got {block_n}): the n-block divisor search "
                         f"steps by lane width")
    kp = _round_up(cin, _LANES)
    np_ = _round_up(cout, _LANES)
    # bn must DIVIDE np_ or the n-grid would floor and skip the trailing
    # output columns; np_ is a multiple of 128, so stepping down by 128
    # always terminates at a divisor.
    bn = min(block_n, np_)
    while np_ % bn:
        bn -= _LANES
    bm = block_m
    bmb = _pick_bm_bwd(kp, np_, block_m)
    mp = _round_up(m, max(bm, bmb))     # bm, bmb: powers of two (checked)
    m_valid = m if mp != m else None

    x2 = x.reshape(m, cin)
    if kp != cin or mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, kp - cin)))
    w2 = w.astype(x.dtype)
    if kp != cin or np_ != cout:
        w2 = jnp.pad(w2, ((0, kp - cin), (0, np_ - cout)))
    prologue = inv is not None
    if prologue:
        inv2 = jnp.pad(inv.astype(jnp.float32).reshape(1, cin),
                       ((0, 0), (0, kp - cin)))
        shift2 = jnp.pad(shift.astype(jnp.float32).reshape(1, cin),
                         ((0, 0), (0, kp - cin)))
    else:
        inv2 = jnp.ones((1, kp), jnp.float32)
        shift2 = jnp.zeros((1, kp), jnp.float32)

    cfg = (prologue, m_valid, bm, bn, bmb, interpret)
    y2, s1, s2 = _conv_bn(cfg, x2, w2, inv2, shift2)
    y = y2[:m, :cout].reshape(n, h, wdim, cout)
    return y, s1[0, :cout], s2[0, :cout]


def supports(cin: int, cout: int) -> bool:
    """Whether the fused kernel handles this 1x1 conv. The backward holds
    W (bf16) + the f32 dW accumulator resident in VMEM, so cin*cout must
    stay <= 1M elements (6 MB resident) — covers every ResNet 1x1 except
    the stage-4 1024->2048 projection, which falls back to XLA."""
    return cin * cout <= 1024 * 1024
