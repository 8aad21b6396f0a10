"""Plain reference of the bottleneck ResNet v1.5 in training mode: float32,
NHWC, batch statistics over the whole batch given, no kernels. It imports
nothing of the program. The parameter tree is the one the weights were drawn
into (``conv_init``, ``bn_init``, ``BottleneckBlock_<i>`` with ``Conv_<j>``,
``FoldedBatchNorm_<j>``, ``conv_proj``, ``norm_proj``, and ``Dense_0``).

    stem:   conv 7x7 / 2 (pad 3) -> BN -> relu -> max pool 3x3 / 2 (pad 1)
    block:  conv 1x1 -> BN -> relu -> conv 3x3 / stride (SAME) -> BN -> relu
            -> conv 1x1 -> BN;  shortcut conv 1x1 / stride -> BN where the
            shape changes;  relu(shortcut + branch)
    head:   mean over H, W -> dense -> softmax cross entropy
    BN:     (x - mean) / sqrt(var + 1e-5) * scale + bias, biased variance

``ops`` supplies the convolutions and the classifier's product, so the
control runs these same lines in a lower precision."""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

EPS = 1e-5


def batch_norm(x, p):
    """(normed x, the batch's statistics)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]
    return y, {"mean": mean, "var": var}


def _block(ops, norm: str, stride: int, x, bp):
    s1, ss = (1, 1), (stride, stride)
    stats = {}
    y = ops.conv(x, bp["Conv_0"]["kernel"], s1, "SAME")
    y, stats[norm + "_0"] = batch_norm(y, bp[norm + "_0"])
    y = ops.conv(jax.nn.relu(y), bp["Conv_1"]["kernel"], ss, "SAME")
    y, stats[norm + "_1"] = batch_norm(y, bp[norm + "_1"])
    y = ops.conv(jax.nn.relu(y), bp["Conv_2"]["kernel"], s1, "SAME")
    y, stats[norm + "_2"] = batch_norm(y, bp[norm + "_2"])
    if "conv_proj" in bp:
        x = ops.conv(x, bp["conv_proj"]["kernel"], ss, "SAME")
        x, stats["norm_proj"] = batch_norm(x, bp["norm_proj"])
    return jax.nn.relu(x + y), stats


def logits(ops, stage_sizes: Sequence[int], norm: str, params: Dict[str, Any],
           images) -> Tuple[jax.Array, Dict[str, Any]]:
    """(logits, every norm's batch statistics in the program's tree)."""
    stats = {}
    x = images.astype(jnp.float32)
    x = ops.conv(x, params["conv_init"]["kernel"], (2, 2), [(3, 3), (3, 3)])
    x, stats["bn_init"] = batch_norm(x, params["bn_init"])
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    i = 0
    for stage, blocks in enumerate(stage_sizes):
        for j in range(blocks):
            stride = 2 if (stage > 0 and j == 0) else 1
            # recomputed in the backward pass: one block input is kept
            x, stats[f"BottleneckBlock_{i}"] = jax.checkpoint(
                lambda x, bp, stride=stride: _block(ops, norm, stride, x, bp)
            )(x, params[f"BottleneckBlock_{i}"])
            i += 1
    x = jnp.mean(x, axis=(1, 2))
    return (ops.einsum("nc,ck->nk", x, params["Dense_0"]["kernel"])
            + params["Dense_0"]["bias"]), stats


def loss_sum(ops, stage_sizes: Sequence[int], norm: str,
             params: Dict[str, Any], images, labels):
    """Sum of the cross entropy over the batch, its size, and the batch
    statistics of every norm. Batch norm ties the rows together, so a block
    is a whole per-chip batch."""
    lg, stats = logits(ops, stage_sizes, norm, params, images)
    logp = jax.nn.log_softmax(lg)
    total = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return total, (jnp.float32(labels.shape[0]), stats)


def running_stats(running, batch, momentum: float = 0.9):
    """The running statistics after one more step, as the program's norm
    keeps them: momentum * running + (1 - momentum) * this batch's (biased
    variance)."""
    return jax.tree.map(lambda r, b: momentum * r + (1.0 - momentum) * b,
                        running, batch)
