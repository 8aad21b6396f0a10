"""Per-leaf norms of a parameter-shaped tree, and seeds as JAX keys."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """``--seed`` may exceed 32 signed bits; fold the high bits in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _is_stacked(path: Tuple, stacked: Tuple[str, ...]) -> bool:
    first = path[0]
    return str(getattr(first, "key", getattr(first, "name", first))) in stacked


def leaf_norms(tree: Any, stacked: Tuple[str, ...] = ()) -> Dict[str, float]:
    """L2 norm of every leaf, read back to the host. A leaf under a top-level
    key in ``stacked`` holds one slice per layer along axis 0 and gives one
    norm per slice (``name[i]``)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    @jax.jit
    def norms(leaves):
        out = []
        for (path, _), x in zip(flat, leaves):
            x = x.astype(jnp.float32)
            if _is_stacked(path, stacked):
                out.append(jnp.sqrt(jnp.sum(
                    x * x, axis=tuple(range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x)))
        return out

    values = jax.device_get(norms([x for _, x in flat]))
    out: Dict[str, float] = {}
    for (path, _), v in zip(flat, values):
        name = jax.tree_util.keystr(path)
        v = np.asarray(v)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            for i, vi in enumerate(v):
                out[f"{name}[{i}]"] = float(vi)
    return out


def leaf_ranks(tree: Any, stacked: Tuple[str, ...] = ()) -> Dict[str, int]:
    """Number of axes of every leaf, under the names ``leaf_norms`` gives (a
    stacked leaf's slices have one axis fewer than the leaf)."""
    out: Dict[str, int] = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if _is_stacked(path, stacked):
            for i in range(x.shape[0]):
                out[f"{name}[{i}]"] = x.ndim - 1
        else:
            out[name] = x.ndim
    return out


def tree_sub(a: Any, b: Any) -> Any:
    return jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(a, b)
