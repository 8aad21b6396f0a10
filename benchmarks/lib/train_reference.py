"""Drive a plain reference through the first training steps.

The family's reference gives ``block_loss_sum(params, *rows) -> (sum of the
per-item losses, (items, what the block says of further state))`` for a block
of rows; the further state (batch statistics) is None where there is none. This adds the blocks up, one
after another on one chip and side by side (one block per chip, then a sum
across chips) on several, divides by the items, and applies plain SGD with
momentum:  trace = g + m * trace;  params = params - lr * trace."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.lib import trees


def _block_grad_fn(block_loss_sum: Callable, devices: Sequence[Any]):
    def fn(params, *rows):
        (total, (count, seen)), grads = jax.value_and_grad(
            lambda p: block_loss_sum(p, *rows), has_aux=True)(params)
        return total, count, grads, seen

    if len(devices) == 1:
        return jax.jit(fn), None
    mesh = Mesh(np.array(devices), ("d",))

    def across(params, *rows):
        return jax.tree.map(lambda x: jax.lax.psum(x, "d"),
                            fn(params, *rows))

    def sharded(params, *rows):
        return jax.shard_map(
            across, mesh=mesh,
            in_specs=(P(),) + (P("d"),) * len(rows), out_specs=P(),
            check_vma=False)(params, *rows)

    return jax.jit(sharded), mesh


def readings(block_loss_sum: Callable, params: Any,
             batches: List[Tuple[Any, ...]], *, lr: float, momentum: float,
             devices: Sequence[Any], rows_per_block: int,
             stacked: Tuple[str, ...] = (),
             keep_rows: int = 0, further: Any = None,
             further_update: Callable = None) -> Dict[str, Any]:
    """Losses of each step, per-leaf norms of the first gradient and of the
    parameters' change after the last step, and every leaf's number of axes
    (``"rank"``: the comparison tells kernels from vectors by it).
    ``batches[k]`` is step k's global batch, rows on axis 0. ``keep_rows`` > 0 plants a fault: only the first
    ``keep_rows`` rows of each batch are used, the mean taken over them.
    ``further`` is state beside the parameters (running batch statistics)
    that ``further_update(further, seen)`` moves by what a step's one block
    saw; its change is read like the parameters' (``"stats"``)."""
    n = len(devices)
    grad_fn, mesh = _block_grad_fn(block_loss_sum, devices)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    @jax.jit
    def sgd(params, trace, grads, count):
        grads = jax.tree.map(lambda g: g / count, grads)
        trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
        params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
        return params, trace, grads

    start, further_start = params, further
    trace = jax.tree.map(jnp.zeros_like, params)
    out: Dict[str, Any] = {"loss": [],
                           "rank": trees.leaf_ranks(params, stacked)}
    for k, batch in enumerate(batches):
        rows = batch[0].shape[0]
        if keep_rows:
            rows = keep_rows
        per_call = rows_per_block * n
        if rows % per_call:
            raise ValueError(f"{rows} rows do not split into blocks of "
                             f"{per_call}")
        total = count = grads = None
        for lo in range(0, rows, per_call):
            if n == 1:
                block = tuple(b[lo:lo + per_call] for b in batch)
            else:
                # chip c's rows lo/n .. of ITS shard: any split of the rows
                # gives the same sums
                per_chip = batch[0].shape[0] // n
                idx = np.concatenate([
                    np.arange(c * per_chip + lo // n,
                              c * per_chip + lo // n + rows_per_block)
                    for c in range(n)])
                block = tuple(jax.device_put(
                    b[idx], NamedSharding(mesh, P("d"))) for b in batch)
            t, c, g, seen = grad_fn(params, *block)
            if further is not None:
                if rows != per_call:
                    raise ValueError("further state needs one block a step")
                further = further_update(further, seen)
            total = t if total is None else total + t
            count = c if count is None else count + c
            grads = g if grads is None else add(grads, g)
        params, trace, mean_grads = sgd(params, trace, grads, count)
        out["loss"].append(float(total) / float(count))
        if k == 0:
            out["grad"] = trees.leaf_norms(mean_grads, stacked)
        del grads, mean_grads
    out["delta"] = trees.leaf_norms(trees.tree_sub(params, start), stacked)
    if further is not None:
        out["stats"] = trees.leaf_norms(trees.tree_sub(further, further_start))
    return out
