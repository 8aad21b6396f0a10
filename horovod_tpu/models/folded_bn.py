"""Lane-folded batch norm — layout-level fix for C<128 feature maps.

Round-2 profile evidence (PERF.md): ResNet-50 training on TPU is
batch-norm bandwidth-bound (~70 % of step time in BN statistics/normalize
fusions), and tensors with C=64 (stem + stage-1 internals) pad the TPU's
128-wide vector lanes 2x — a pallas BN kernel could not win at C=64
because the traffic amplification is imposed by the LAYOUT, not the
lowering.

The fix exploited here: for NHWC with C < 128 and W even, the bitcast-free
reshape ``(N, H, W, C) -> (N, H, W/k, k*C)`` (k = 128/C) packs k spatial
columns into a full 128-lane row. Per-channel statistics are recovered
exactly — channel c's sum equals the folded view's sums at lanes
``c, c+C, ..., c+(k-1)C`` added together — and the normalize applies
per-channel parameters tiled k times, elementwise in the folded view. Both
passes then read/write the tensor at full lane occupancy. Numerics are
bit-identical reductions up to float reassociation; interface and running
statistics match ``flax.linen.BatchNorm``.

(Reference framework has no analogue — this is TPU-layout-specific; the
role corresponds to the reference's hand-tuned CUDA BN in
torch/sync_batch_norm.py only in spirit.)
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax


class FoldedBatchNorm(nn.Module):
    """Drop-in for ``nn.BatchNorm`` (use_running_average/momentum/epsilon/
    dtype/axis_name subset) that computes through the lane-folded view when
    it helps and transparently falls back to plain behavior otherwise."""

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None
    axis_name: Optional[str] = None
    scale_init: Any = nn.initializers.ones
    bias_init: Any = nn.initializers.zeros
    lane_width: int = 128          # TPU vector lane count

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        compute_dtype = self.dtype or x.dtype
        x = x.astype(compute_dtype)
        scale = self.param("scale", self.scale_init, (c,))
        bias = self.param("bias", self.bias_init, (c,))
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((c,), jnp.float32))

        k = self.lane_width // c if c and self.lane_width % c == 0 else 1
        fold = (k > 1 and x.ndim >= 2 and not self.use_running_average
                and x.shape[-2] % k == 0)

        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            n = 1
            for d in x.shape[:-1]:
                n *= d
            if fold:
                xf = x.reshape(x.shape[:-2]
                               + (x.shape[-2] // k, k * c))   # free reshape
                sums = jnp.sum(xf.astype(jnp.float32),
                               axis=tuple(range(xf.ndim - 1)))
                sqs = jnp.sum(jnp.square(xf.astype(jnp.float32)),
                              axis=tuple(range(xf.ndim - 1)))
                # lane (j*C + c) holds channel c's j-th spatial phase
                sums = sums.reshape(k, c).sum(0)
                sqs = sqs.reshape(k, c).sum(0)
            else:
                sums = jnp.sum(x.astype(jnp.float32),
                               axis=tuple(range(x.ndim - 1)))
                sqs = jnp.sum(jnp.square(x.astype(jnp.float32)),
                              axis=tuple(range(x.ndim - 1)))
            if self.axis_name is not None:
                sums = lax.psum(sums, self.axis_name)
                sqs = lax.psum(sqs, self.axis_name)
                n = n * lax.axis_size(self.axis_name)
            mean = sums / n
            var = jnp.maximum(sqs / n - jnp.square(mean), 0.0)
            # Running stats use the biased batch variance, matching
            # flax.linen.BatchNorm's update rule (and its is_initializing
            # guard: the init pass must not count as a step).
            if not self.is_initializing():
                ra_mean.value = (self.momentum * ra_mean.value
                                 + (1.0 - self.momentum) * mean)
                ra_var.value = (self.momentum * ra_var.value
                                + (1.0 - self.momentum) * var)

        inv = lax.rsqrt(var + self.epsilon) * scale
        shift = bias - mean * inv
        inv = inv.astype(compute_dtype)
        shift = shift.astype(compute_dtype)
        if fold:
            xf = x.reshape(x.shape[:-2] + (x.shape[-2] // k, k * c))
            y = xf * jnp.tile(inv, k) + jnp.tile(shift, k)
            return y.reshape(x.shape)
        return x * inv + shift
