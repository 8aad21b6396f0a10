"""In-graph tensor fusion: pack many small arrays into one flat buffer per
dtype, run one collective, unpack.

This is the TPU-native analogue of the reference's fusion buffer
(reference: fusion_buffer_manager.h:31-47 — one persistent 128 MiB buffer per
device/framework/stream; greedy response packing controller.cc:887
FuseResponses; batched pack/unpack CUDA kernels cuda/cuda_kernels.cu).
On TPU there is no persistent buffer to manage: the pack (concat of raveled
arrays), the collective, and the unpack (slice + reshape) are traced into one
XLA program, so the copies fuse with the collective's own buffer preparation
and the "fusion buffer" lives only inside the executable. What remains valuable
is the *batching decision* — amortizing dispatch overhead by issuing one fused
collective for many tensors — which the eager coordinator makes per cycle
(ops/coordinator.py) and this module implements in-graph.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def leaf_sizes(tree) -> List[int]:
    """Per-leaf byte sizes of a pytree of arrays / ShapeDtypeStructs, in
    ``jax.tree.leaves`` order — the input both :func:`expected_manifest`
    (the bucket schedule is planned over these) and the cost tier's
    memory accounting (analysis/cost.py) are driven from. Works on
    abstract leaves: nothing is materialized."""
    return [int(np.prod(l.shape, dtype=np.int64))
            * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(tree)]


def fuse_apply(fn: Callable[[jax.Array], jax.Array],
               xs: Sequence[jax.Array],
               batch: bool = True) -> List[jax.Array]:
    """Apply an elementwise-compatible collective ``fn`` (e.g. a psum) to all
    arrays as one fused buffer per dtype; returns outputs in input order.

    Structure-preserving: shapes/dtypes of outputs match inputs. Arrays of the
    same dtype are raveled and concatenated (the pack), ``fn`` runs once per
    dtype (one collective), then slices are reshaped back (the unpack).

    ``batch=False`` (HOROVOD_BATCH_D2D_MEMCOPIES=0, ref cuda_kernels.cu
    batched-memcpy toggle) skips the pack: ``fn`` is applied per array —
    still one traced program, but one collective per tensor.
    """
    xs = list(xs)
    if not xs:
        return []
    if not batch or len(xs) == 1:
        return [fn(x) for x in xs]

    by_dtype: Dict[jnp.dtype, List[int]] = {}
    for i, x in enumerate(xs):
        by_dtype.setdefault(jnp.asarray(x).dtype, []).append(i)

    out: List[jax.Array] = [None] * len(xs)  # type: ignore[list-item]
    for dtype, idxs in by_dtype.items():
        parts = [jnp.ravel(xs[i]) for i in idxs]
        sizes = [p.shape[0] for p in parts]
        fused = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        result = fn(fused)
        offset = 0
        for i, size in zip(idxs, sizes):
            out[i] = jnp.reshape(
                jax.lax.dynamic_slice_in_dim(result, offset, size, 0),
                jnp.shape(xs[i]))
            offset += size
    return out


def flatten_for_fusion(
    xs: Sequence[jax.Array],
) -> Tuple[jax.Array, List[Tuple[Tuple[int, ...], int]]]:
    """Pack same-dtype arrays into one flat buffer; returns (buffer, specs)
    where specs[i] = (shape, size). Raises on mixed dtypes."""
    dtypes = {jnp.asarray(x).dtype for x in xs}
    if len(dtypes) != 1:
        raise ValueError(f"flatten_for_fusion needs uniform dtype, got {dtypes}")
    parts = [jnp.ravel(x) for x in xs]
    specs = [(tuple(np.shape(x)), int(np.prod(np.shape(x), dtype=np.int64)))
             for x in xs]
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0], specs


def unflatten_from_fusion(buffer: jax.Array, specs) -> List[jax.Array]:
    out = []
    offset = 0
    for shape, size in specs:
        out.append(jnp.reshape(
            jax.lax.dynamic_slice_in_dim(buffer, offset, size, 0), shape))
        offset += size
    return out


def plan_fusion_bins(sizes_bytes: Sequence[int], threshold: int) -> List[List[int]]:
    """Greedy bin-packing of tensor indices under the fusion threshold with
    look-ahead skip (the reference's FuseResponses controller.cc:887-986):
    walk the queue in order, adding tensors whose bytes still fit the current
    bin, skipping (not stopping at) ones that don't.

    Dispatches to the native planner (csrc/core.cc hvd_plan_fusion_bins)
    when built; this Python body is the fallback and the behavioral spec —
    both produce identical bins (asserted in tests/test_native.py)."""
    from horovod_tpu import native
    native_bins = native.plan_fusion_bins(sizes_bytes, threshold)
    if native_bins is not None:
        return native_bins
    return _plan_fusion_bins_py(sizes_bytes, threshold)


def _plan_fusion_bins_py(sizes_bytes: Sequence[int],
                         threshold: int) -> List[List[int]]:
    bins: List[List[int]] = []
    remaining = list(range(len(sizes_bytes)))
    while remaining:
        bin_idxs: List[int] = []
        acc = 0
        leftover: List[int] = []
        for i in remaining:
            b = sizes_bytes[i]
            if not bin_idxs or acc + b <= threshold:
                bin_idxs.append(i)
                acc += b
            else:
                leftover.append(i)
        bins.append(bin_idxs)
        remaining = leftover
    return bins


def expected_manifest(leaf_sizes_bytes: Sequence[int],
                      bucket_bytes: int,
                      declared: Sequence[dict] = (),
                      compression=None,
                      dcn: Optional[dict] = None) -> dict:
    """Expected-collectives manifest for one fused gradient sync — the
    build-time contract the IR verifier (HVD502, analysis/ir.py) checks
    the compiled step's optimized HLO against.

    The bucket schedule (parallel/distributed._bucket_reverse_order,
    exactly what `_sync_leaves_fused` traces) determines the expected
    all-reduce count and the largest single collective payload;
    ``declared`` appends the model's intended resharding collectives
    (TP logit all-gathers, SP ring collective-permutes, EP all-to-alls)
    as ``{"op": "all-gather", "count": 2, "bytes": N, "reason": ...}``
    budget entries. Anything the partitioner inserts beyond these
    budgets is an HVD502 finding.

    ``compression`` auto-declares the wire tier: pass the SAME
    ``compression=`` value the DistributedOptimizer got (a Compression.*
    class or tier string; None still honors the
    HOROVOD_GRADIENT_COMPRESSION knob, which overrides either way). An
    active tier scales the expected all-reduce payloads to the wire
    itemsize (leaf sizes are f32 bytes) and stamps ``expect_compression``
    + ``wire_dtype`` so ``hvd.verify_step`` silences HVD505 for converts
    to exactly that dtype — an UNdeclared (stray) narrow cast feeding a
    psum still trips.

    ``bucket_bytes`` <= 0 means the single-fused-buffer schedule (one
    all-reduce for everything).

    ``dcn``: per-tier declaration for the two-level DCN schedule
    (HOROVOD_DCN_SCHEDULE=two_level, docs/hierarchical.md) — a dict with
    ``ici_world`` (ranks per slice) and ``dcn_world`` (slices). Each
    bucket then expects THREE collectives instead of one: an intra-slice
    reduce-scatter and all-gather of the (ICI-padded) full bucket, and a
    cross-slice all-reduce of only the 1/ici_world shard — in the wire
    dtype when ``compression`` is active, since the codec narrows
    exactly the slow stage. The all-gather budget is what keeps the
    tier's gather stage out of HVD502's implicit-resharding findings;
    the wire_dtype stamp is what keeps HVD505 narrow on the cross-DCN
    reduction while still tripping on any STRAY narrow cast.
    """
    from horovod_tpu import compression as compr
    sizes = [int(s) for s in leaf_sizes_bytes]
    codec = compr.wire_codec(compression)
    entries = []
    if sizes:
        if bucket_bytes and bucket_bytes > 0:
            buckets = _plan_buckets_by_bytes(sizes, int(bucket_bytes))
        else:
            buckets = [list(range(len(sizes)))]
        top = max(sum(sizes[i] for i in b) for b in buckets)
        if dcn and int(dcn.get("dcn_world", 1)) > 1:
            n_ici = max(int(dcn.get("ici_world", 1)), 1)
            n_dcn = int(dcn["dcn_world"])
            # the bucket is padded to a multiple of the ICI world before
            # the reduce-scatter (elements, assuming 4-byte leaves)
            elems = -(-(top // 4) // n_ici) * n_ici
            padded = elems * 4
            shard = (elems // n_ici) * 4
            if codec is not None:
                shard = (shard // 4) * codec.wire_itemsize \
                    + (4 if codec.scaled else 0)
            reason = (f"two-level DCN tier ({len(sizes)} leaves, "
                      f"bucket_bytes={int(bucket_bytes)}, "
                      f"ici={n_ici}, slices={n_dcn}"
                      + (f", cross wire={codec.tier}" if codec else "")
                      + ")")
            entries.append({"op": "reduce-scatter", "count": len(buckets),
                            "bytes": padded,
                            "reason": f"{reason}: intra-slice stage"})
            entries.append({"op": "all-reduce", "count": len(buckets),
                            "bytes": shard,
                            "reason": f"{reason}: cross-slice shard"})
            entries.append({"op": "all-gather", "count": len(buckets),
                            "bytes": padded,
                            "reason": f"{reason}: intra-slice gather"})
        else:
            if codec is not None:
                # leaf sizes are stated in f32 bytes; the wire moves
                # wire_itemsize per element (+ a scalar scale per bucket
                # for the fp8 tiers — too small to budget)
                top = (top // 4) * codec.wire_itemsize + \
                    (4 if codec.scaled else 0)
            entries.append({
                "op": "all-reduce",
                "count": len(buckets),
                "bytes": top,
                "reason": f"gradient bucket schedule ({len(sizes)} "
                          f"leaves, bucket_bytes={int(bucket_bytes)}"
                          + (f", wire={codec.tier}" if codec else "")
                          + ")",
            })
    entries.extend(dict(d) for d in declared)
    out = {
        "bucket_bytes": int(bucket_bytes),
        "n_leaves": len(sizes),
        "total_gradient_bytes": sum(sizes),
        "entries": entries,
    }
    if dcn and int(dcn.get("dcn_world", 1)) > 1 and sizes:
        out["tiers"] = {
            "schedule": "two_level",
            "ici_world": max(int(dcn.get("ici_world", 1)), 1),
            "dcn_world": int(dcn["dcn_world"]),
            "cross_wire_dtype": str(jnp.dtype(codec.wire_dtype))
            if codec is not None else None,
        }
    if codec is not None:
        out["expect_compression"] = True
        out["wire_dtype"] = str(jnp.dtype(codec.wire_dtype))
    return out


def _plan_buckets_by_bytes(sizes_bytes: Sequence[int],
                           bucket_bytes: int) -> List[List[int]]:
    """The bucket schedule `_sync_leaves_fused` produces: contiguous
    chunks over the leaf list in REVERSE order, each at most
    ``bucket_bytes`` (every bucket holds at least one leaf)."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    acc = 0
    for i in reversed(range(len(sizes_bytes))):
        b = int(sizes_bytes[i])
        if cur and acc + b > bucket_bytes:
            buckets.append(cur)
            cur, acc = [], 0
        cur.append(i)
        acc += b
    if cur:
        buckets.append(cur)
    return buckets


def group_leaves_by_axes(tree, sync_axes):
    """Align a (possibly coarse) ``sync_axes`` tree with ``tree``'s leaves
    and group leaf indices by their normalized axes tuple.

    ``sync_axes`` mirrors ``tree`` with tuple-of-axis-names leaves; a tuple
    may sit at an interior position and covers the whole subtree (the
    coarse form ``jax.tree.map``'s prefix semantics allowed). Returns
    ``(treedef, leaves, {axes_tuple: [leaf_index, ...]})`` where axes
    tuples are filtered of falsy entries. Structure mismatches raise
    jax's usual tree-structure error at THIS boundary instead of
    surfacing as silent None leaves downstream.

    Shared by the gradient-sync paths (parallel/distributed.py,
    parallel/trainer.sync_gradients) so the grouping/alignment logic has
    one home.
    """
    is_axes = lambda x: isinstance(x, tuple) or x is None  # noqa: E731
    # Expand coarse axes leaves over the subtrees they cover: tree_map with
    # sync_axes as the leading tree hands each axes leaf its matching
    # subtree of ``tree``.
    expanded = jax.tree_util.tree_map(
        lambda a, sub: jax.tree_util.tree_map(lambda _: a, sub),
        sync_axes, tree, is_leaf=is_axes)
    axes_leaves = jax.tree_util.tree_leaves(
        expanded, is_leaf=is_axes)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(axes_leaves) != len(leaves):
        raise ValueError(
            f"sync_axes resolves to {len(axes_leaves)} leaves but the "
            f"gradient tree has {len(leaves)}")
    groups: Dict[Tuple, List[int]] = {}
    for i, a in enumerate(axes_leaves):
        a = a if isinstance(a, tuple) else (a,)
        groups.setdefault(tuple(x for x in a if x), []).append(i)
    return treedef, leaves, groups
