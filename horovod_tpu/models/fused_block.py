"""Fused-BN bottleneck block — the Pallas conv+stats path of ResNet.

Composes ``ops/pallas/conv_bn.conv1x1_bn_stats`` into the v1.5 bottleneck
so batch-norm costs no separate HBM passes on the 1x1 convs:

- each 1x1 conv emits its output's per-channel sum/sumsq from the kernel
  epilogue (the BN statistics pass disappears),
- the 3x3 conv's input is normalized by one XLA elementwise pass (the 3x3
  itself stays on XLA's conv, which is already MXU-efficient),
- the expand conv consumes the RAW 3x3 output, applying normalize+ReLU in
  its Pallas prologue (the normalized activation is never materialized).

Statistics→parameter math (mean/var/running stats/scale/bias) runs in
plain JAX on (C,)-vectors — negligible — and matches
``flax.linen.BatchNorm`` semantics (biased batch variance in the running
update, is_initializing guard, optional cross-replica psum via
``axis_name``, ref horovod/torch/sync_batch_norm.py role).

Parameter-equivalence with the unfused ``BottleneckBlock`` is exact: same
shapes, same initializers (lecun-normal convs; zero-init gamma on the
last BN); tests map the trees by name and assert outputs/gradients match.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.pallas import conv_bn
from horovod_tpu.ops.pallas.conv_bn import conv1x1_bn_stats

ModuleDef = Any
_LANES = 128


def _conv1x1_stats(x, w, inv=None, shift=None, strides=(1, 1),
                   interpret=False):
    """Fused Pallas kernel when its VMEM budget allows, else the XLA
    composition (prologue elementwise + conv + stats reduce) — same
    contract either way."""
    cin, cout = w.shape[-2], w.shape[-1]
    if conv_bn.supports(cin, cout) or interpret:
        return conv1x1_bn_stats(x, w, inv, shift, strides=strides,
                                interpret=interpret)
    if inv is not None:
        x = jnp.maximum(x * inv.astype(x.dtype) + shift.astype(x.dtype), 0)
    y = lax.conv_general_dilated(
        x, w.reshape(1, 1, cin, cout).astype(x.dtype), strides, "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    s1, s2 = channel_sums(y)
    return y, s1, s2


def channel_sums(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """f32 per-channel (sum, sum of squares) over all leading dims, through
    the lane-folded view when C < 128 divides the lane width (the
    models/folded_bn trick: full 128-lane occupancy for C=64 tensors)."""
    c = x.shape[-1]
    k = _LANES // c if c and _LANES % c == 0 else 1
    if k > 1 and x.ndim >= 2 and x.shape[-2] % k == 0:
        xf = x.reshape(x.shape[:-2] + (x.shape[-2] // k, k * c))
        s1 = jnp.sum(xf.astype(jnp.float32), axis=tuple(range(xf.ndim - 1)))
        s2 = jnp.sum(jnp.square(xf.astype(jnp.float32)),
                     axis=tuple(range(xf.ndim - 1)))
        return s1.reshape(k, c).sum(0), s2.reshape(k, c).sum(0)
    s1 = jnp.sum(x.astype(jnp.float32), axis=tuple(range(x.ndim - 1)))
    s2 = jnp.sum(jnp.square(x.astype(jnp.float32)),
                 axis=tuple(range(x.ndim - 1)))
    return s1, s2


class FusedBottleneckBlock(nn.Module):
    """Drop-in for ``BottleneckBlock`` (same constructor signature, same
    parameter shapes/initializers) computing train-mode BN through the
    fused Pallas kernels. ``norm`` must be a ``functools.partial`` of
    nn.BatchNorm/FoldedBatchNorm — its keywords (use_running_average,
    momentum, epsilon, dtype, axis_name) configure the fused BN math."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)
    interpret: bool = False

    def _norm_kw(self, key, default=None):
        return getattr(self.norm, "keywords", {}).get(key, default)

    def _bn(self, name: str, s1, s2, count, scale_init=nn.initializers.ones):
        """BN statistics -> (inv, shift) affine vectors + running-stat
        update (flax BatchNorm-equivalent math on (C,) vectors)."""
        c = s1.shape[0]
        momentum = self._norm_kw("momentum", 0.9)
        eps = self._norm_kw("epsilon", 1e-5)
        axis_name = self._norm_kw("axis_name")
        scale = self.param(f"{name}_scale", scale_init, (c,))
        bias = self.param(f"{name}_bias", nn.initializers.zeros, (c,))
        ra_mean = self.variable("batch_stats", f"{name}_mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", f"{name}_var",
                               lambda: jnp.ones((c,), jnp.float32))
        if axis_name is not None:
            s1 = lax.psum(s1, axis_name)
            s2 = lax.psum(s2, axis_name)
            count = count * lax.axis_size(axis_name)
        mean = s1 / count
        var = jnp.maximum(s2 / count - jnp.square(mean), 0.0)
        if not self.is_initializing():
            ra_mean.value = momentum * ra_mean.value + (1 - momentum) * mean
            ra_var.value = momentum * ra_var.value + (1 - momentum) * var
        inv = lax.rsqrt(var + eps) * scale
        shift = bias - mean * inv
        return inv, shift

    def _bn_eval_c(self, name: str, c: int,
                   scale_init=nn.initializers.ones):
        """(inv, shift) from the running statistics (eval path); declares
        the same names as _bn so both modes build one parameter set."""
        eps = self._norm_kw("epsilon", 1e-5)
        scale = self.param(f"{name}_scale", scale_init, (c,))
        bias = self.param(f"{name}_bias", nn.initializers.zeros, (c,))
        ra_mean = self.variable("batch_stats", f"{name}_mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", f"{name}_var",
                               lambda: jnp.ones((c,), jnp.float32))
        inv = lax.rsqrt(ra_var.value + eps) * scale
        shift = bias - ra_mean.value * inv
        return inv, shift

    @nn.compact
    def __call__(self, x):
        if self.act is not nn.relu:
            # The Pallas prologue hardcodes ReLU (jnp.maximum in
            # _fwd_kernel and the XLA fallback); any other act would be
            # silently replaced for the middle activation only.
            raise ValueError(
                "FusedBottleneckBlock supports act=nn.relu only (the "
                "normalize+act prologue is fused into the conv kernel); "
                "use fused_conv_bn=False for other activations")
        f = self.filters
        cin = x.shape[-1]
        dtype = self._norm_kw("dtype") or x.dtype
        eval_mode = bool(self._norm_kw("use_running_average", False))
        kinit = nn.linear.default_kernel_init      # nn.Conv's default
        w1 = self.param("conv1_kernel", kinit, (1, 1, cin, f))
        w3 = self.param("conv3_kernel", kinit, (1, 1, f, 4 * f))
        needs_proj = (x.shape[-1] != 4 * f or self.strides != (1, 1))
        if needs_proj:
            wp = self.param("proj_kernel", kinit, (1, 1, cin, 4 * f))
        x = x.astype(dtype)

        if eval_mode:
            return self._eval_path(x, w1, w3,
                                   wp if needs_proj else None)

        # conv1 (reduce): plain input, stats epilogue
        y1, s1a, s1b = _conv1x1_stats(
            x, w1.astype(dtype), interpret=self.interpret)
        n1 = float(y1.shape[0] * y1.shape[1] * y1.shape[2])
        inv1, shift1 = self._bn("bn1", s1a, s1b, n1)
        z1 = self.act(y1 * inv1.astype(dtype) + shift1.astype(dtype))

        # conv2 (3x3): XLA conv; its BN stats via one (lane-folded) reduce
        y2 = self.conv(f, (3, 3), self.strides, name="Conv_0")(z1)
        s2a, s2b = channel_sums(y2)
        n2 = float(y2.shape[0] * y2.shape[1] * y2.shape[2])
        inv2, shift2 = self._bn("bn2", s2a, s2b, n2)

        # conv3 (expand): normalize+ReLU of y2 in the prologue, stats out
        y3, s3a, s3b = _conv1x1_stats(
            y2, w3.astype(dtype), inv2, shift2, interpret=self.interpret)
        inv3, shift3 = self._bn("bn3", s3a, s3b, n2,
                                scale_init=nn.initializers.zeros)

        if needs_proj:
            yp, spa, spb = _conv1x1_stats(
                x, wp.astype(dtype), strides=self.strides,
                interpret=self.interpret)
            invp, shiftp = self._bn("bnp", spa, spb, n2)
            residual = yp * invp.astype(dtype) + shiftp.astype(dtype)
        else:
            residual = x
        return self.act(y3 * inv3.astype(dtype) + shift3.astype(dtype)
                        + residual)

    # -- eval: plain composition over the SAME parameters -------------------
    def _eval_path(self, x, w1, w3, wp):
        f = self.filters
        dtype = x.dtype

        def conv1x1(v, w, strides=(1, 1)):
            return lax.conv_general_dilated(
                v, w.astype(dtype), strides, "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        inv1, shift1 = self._bn_eval_c("bn1", f)
        z1 = self.act(conv1x1(x, w1) * inv1.astype(dtype)
                      + shift1.astype(dtype))
        y2 = self.conv(f, (3, 3), self.strides, name="Conv_0")(z1)
        inv2, shift2 = self._bn_eval_c("bn2", f)
        z2 = self.act(y2 * inv2.astype(dtype) + shift2.astype(dtype))
        inv3, shift3 = self._bn_eval_c(
            "bn3", 4 * f, scale_init=nn.initializers.zeros)
        y3n = (conv1x1(z2, w3) * inv3.astype(dtype)
               + shift3.astype(dtype))
        if wp is not None:
            invp, shiftp = self._bn_eval_c("bnp", 4 * f)
            residual = (conv1x1(x, wp, self.strides) * invp.astype(dtype)
                        + shiftp.astype(dtype))
        else:
            residual = x
        return self.act(y3n + residual)


# ---------------------------------------------------------------------------
# Checkpoint conversion: fused <-> plain parameter trees
# ---------------------------------------------------------------------------
# The fused block flattens its parameters (conv1_kernel, bn1_scale, ...)
# where the plain BottleneckBlock nests submodules (Conv_0/kernel,
# BatchNorm_0/scale, ...), so toggling ``fused_conv_bn`` on an existing
# ResNet invalidates previously saved checkpoints. These utilities map
# between the two layouts (same arrays, renamed paths) so checkpoints
# survive the toggle.

def translate_fused_key(key: Tuple[str, ...]) -> Tuple[str, ...]:
    """Fused-model flat variable path -> the plain model's path for the
    SAME array (both directions are bijective; see
    :func:`plain_to_fused_variables`)."""
    bn_map = {"bn1": "BatchNorm_0", "bn2": "BatchNorm_1",
              "bn3": "BatchNorm_2", "bnp": "norm_proj"}
    out: list = []
    for part in key:
        part = part.replace("FusedBottleneckBlock", "BottleneckBlock")
        if part == "conv1_kernel":
            out += ["Conv_0", "kernel"]
        elif part == "conv3_kernel":
            out += ["Conv_2", "kernel"]
        elif part == "proj_kernel":
            out += ["conv_proj", "kernel"]
        elif part == "Conv_0" and "Bottleneck" in "".join(out[-1:]):
            out += ["Conv_1"]          # the fused block's 3x3
        elif "_" in part and part.split("_")[0] in bn_map:
            bn, field = part.split("_", 1)
            out += [bn_map[bn], field]
        else:
            out.append(part)
    return tuple(out)


def plain_to_fused_variables(fused_template, plain_vars):
    """Rebuild a fused-model variable tree from a plain-model checkpoint.

    ``fused_template`` supplies the fused tree's structure (e.g. from
    ``fused_model.init(...)`` or ``jax.eval_shape`` of it); every leaf is
    replaced by the corresponding array of ``plain_vars``. Raises KeyError
    naming the first unmatched path."""
    from flax.core import freeze, unfreeze
    from flax.traverse_util import flatten_dict, unflatten_dict
    flat_plain = flatten_dict(unfreeze(plain_vars))
    out = {}
    for k in flatten_dict(unfreeze(fused_template)):
        pk = translate_fused_key(k)
        if pk not in flat_plain:
            raise KeyError(
                f"no plain-model variable {'/'.join(pk)} for fused path "
                f"{'/'.join(k)} — are the two models the same architecture?")
        out[k] = flat_plain[pk]
    return freeze(unflatten_dict(out))


def fused_to_plain_variables(plain_template, fused_vars):
    """Inverse of :func:`plain_to_fused_variables`: save a fused-model
    state into the plain model's checkpoint layout."""
    from flax.core import freeze, unfreeze
    from flax.traverse_util import flatten_dict, unflatten_dict
    flat_fused = flatten_dict(unfreeze(fused_vars))
    renamed = {translate_fused_key(k): v for k, v in flat_fused.items()}
    out = {}
    for k in flatten_dict(unfreeze(plain_template)):
        if k not in renamed:
            raise KeyError(
                f"no fused-model variable maps to plain path {'/'.join(k)}")
        out[k] = renamed[k]
    return freeze(unflatten_dict(out))
