"""In-jit collective primitives over named mesh axes — the TPU data plane.

This is the TPU-native equivalent of the reference's backend op layer
(reference: horovod/common/ops/ — NCCLAllreduce nccl_operations.cc:185,
NCCLAllgather :981, NCCLBroadcast, NCCLAlltoall :1156, NCCLReducescatter :1226,
MPI/Gloo/CCL variants). Where the reference hand-schedules NCCL calls on private
CUDA streams, here every collective is a traceable function over one or more
named mesh axes that XLA lowers onto ICI/DCN — fusion with neighbouring compute,
stream scheduling and topology-aware algorithm choice (ring vs tree vs torus)
belong to the compiler.

Semantics parity notes:
- 6 reduce ops (AVERAGE/SUM/ADASUM/MIN/MAX/PRODUCT, ref message.h:43) with
  prescale/postscale factors (ref message.h:59, collective_operations.h:88).
- Process sets lower to ``axis_index_groups`` — XLA's native subgroup
  partition — instead of sub-communicators (ref process_set.h:26).
- allgather concatenates along dim 0 (ref collective_operations.h:137-152);
  uneven first dims ("allgatherv") are handled by the eager layer via
  pad-to-max since SPMD shards must be shape-uniform.
- alltoall splits/concats along dim 0 (ref EnqueueTensorAlltoall
  operations.cc:1881); reducescatter splits dim 0 across ranks (ref
  collective_operations.h:282-295).

All functions must be called inside shard_map/pmap tracing with the given axis
name(s) bound.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.ops.reduce_ops import ReduceOp, check_supported
from horovod_tpu.runtime.topology import CROSS_AXIS, DCN_AXIS, HVD_AXIS, \
    LOCAL_AXIS

AxisSpec = Union[str, Tuple[str, ...]]


def _axes_tuple(axis: AxisSpec) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def axis_rank(axis: AxisSpec = HVD_AXIS):
    """Per-chip rank along axis/axes (row-major over multiple axes)."""
    axes = _axes_tuple(axis)
    r = lax.axis_index(axes[0])
    for a in axes[1:]:
        r = r * lax.axis_size(a) + lax.axis_index(a)
    return r


def axis_size(axis: AxisSpec = HVD_AXIS) -> int:
    return int(np.prod([lax.axis_size(a) for a in _axes_tuple(axis)]))


def _resolve_groups(process_set, axis: AxisSpec):
    """Returns (axis_index_groups, per-rank group-size table, per-rank
    group-rank table), or (None, None, None) for the global set.
    Static — computed at trace time.

    Group entries are LINEARIZED ranks over the axes tuple (row-major,
    outermost first) — exactly XLA's ``axis_index_groups`` semantics when a
    collective names several mesh axes — so subgroup collectives compose
    with hierarchical (cross, local) meshes; the reference likewise keeps
    per-set communicators independent of the hierarchy (process_set.h:26)."""
    if process_set is None or process_set.process_set_id == 0:
        return None, None, None
    groups = process_set.axis_index_groups()
    world = sum(len(g) for g in groups)
    gsize = np.ones((world,), np.int32)
    grank = np.zeros((world,), np.int32)
    for g in groups:
        for i, r in enumerate(g):
            gsize[r] = len(g)
            grank[r] = i
    return groups, jnp.asarray(gsize), jnp.asarray(grank)


def _apply_scale(x, factor):
    if factor is None or factor == 1.0:
        return x
    if jnp.issubdtype(x.dtype, jnp.integer):
        return (x.astype(jnp.float64 if x.dtype == jnp.int64 else jnp.float32)
                * factor).astype(x.dtype)
    return x * jnp.asarray(factor, dtype=x.dtype)


def _join_neutral(op: ReduceOp, dtype):
    """Identity element a joined rank contributes (ref JoinOp
    collective_operations.h:312: joined ranks supply zero tensors; MIN/MAX/
    PRODUCT need their own identities)."""
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.ADASUM):
        # Zero is also Adasum's identity on the flat butterfly: the
        # pairwise combine's zero-norm guard yields pairwise(a, 0) = a at
        # every level (ops/adasum._pairwise_adasum; ref adasum.h:420-436).
        # The hierarchical (cross, local) path additionally needs the
        # joined_ranks list to fix its local-mean denominator — zero is
        # NOT the identity of a pmean (adasum_allreduce join accounting).
        return jnp.zeros((), dtype)
    if op == ReduceOp.MIN:
        return jnp.asarray(jnp.inf if jnp.issubdtype(dtype, jnp.floating)
                           else jnp.iinfo(dtype).max, dtype)
    if op == ReduceOp.MAX:
        return jnp.asarray(-jnp.inf if jnp.issubdtype(dtype, jnp.floating)
                           else jnp.iinfo(dtype).min, dtype)
    if op == ReduceOp.PRODUCT:
        return jnp.ones((), dtype)
    raise ValueError(f"join does not support {op}")


def allreduce(
    x: jax.Array,
    op: ReduceOp = ReduceOp.SUM,
    axis: AxisSpec = HVD_AXIS,
    process_set=None,
    prescale_factor: Optional[float] = None,
    postscale_factor: Optional[float] = None,
    joined_ranks: Tuple[int, ...] = (),
) -> jax.Array:
    """Allreduce across the axis (ref NCCLAllreduce nccl_operations.cc:185).

    ADASUM here dispatches to the library composite (ops/adasum.py); MIN/MAX
    lower to pmin/pmax, PRODUCT to an all_gather+prod contraction (XLA has no
    product collective; gather+reduce keeps it one ICI pass).

    ``joined_ranks`` (static tuple): ranks that Joined (exhausted their
    data, ref Request::JOIN message.h:65) contribute the op's identity, and
    AVERAGE divides by the number of ACTIVE ranks only (ref
    controller.cc:269-327 joined_size accounting).
    """
    op = check_supported(op)
    groups, gsize, grank = _resolve_groups(process_set, axis)
    axes = _axes_tuple(axis)

    if joined_ranks:
        idx = axis_rank(axis)
        active = jnp.logical_not(
            jnp.isin(idx, jnp.asarray(joined_ranks, jnp.int32)))
        x = jnp.where(active, x, _join_neutral(op, x.dtype))
        if op == ReduceOp.AVERAGE:
            out = lax.psum(_apply_scale(x, prescale_factor), axes,
                           axis_index_groups=groups)
            if groups is None:
                denom = jnp.asarray(
                    max(axis_size(axis) - len(joined_ranks), 1), out.dtype)
            else:
                # Per-set join accounting (ref process_set.h:26 per-set
                # joined state, controller.cc:269-327): each rank divides
                # by ITS group's active-member count; singleton
                # (non-member) groups stay at 1.
                world = sum(len(g) for g in groups)
                jset = set(joined_ranks)
                counts = np.ones((world,), np.int64)
                for g in groups:
                    c = max(len([r for r in g if r not in jset]), 1)
                    for r in g:
                        counts[r] = c
                denom = jnp.asarray(counts)[idx].astype(out.dtype)
            return _apply_scale(out / denom, postscale_factor)

    x = _apply_scale(x, prescale_factor)
    if op == ReduceOp.ADASUM:
        from horovod_tpu.ops.adasum import adasum_allreduce
        # joined_ranks threaded through: zeros are Adasum's identity on the
        # flat butterfly, but the hierarchical path's local averaging must
        # divide by ACTIVE counts (ops/adasum.py join accounting).
        out = adasum_allreduce(x, axis=axis, process_set=process_set,
                               joined_ranks=joined_ranks)
    elif op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        out = lax.psum(x, axes, axis_index_groups=groups)
        if op == ReduceOp.AVERAGE:
            if groups is None:
                out = out / axis_size(axis)
            else:
                n = gsize[axis_rank(axis)]
                out = out / n.astype(out.dtype)
    elif op == ReduceOp.MIN:
        out = lax.pmin(x, axes, axis_index_groups=groups)
    elif op == ReduceOp.MAX:
        out = lax.pmax(x, axes, axis_index_groups=groups)
    elif op == ReduceOp.PRODUCT:
        if groups is None:
            gathered = lax.all_gather(x, axes, axis=0)
            out = jnp.prod(gathered, axis=0)
        else:
            # Shape-changing collectives need size-uniform groups, so a
            # subgroup product gathers member values via a one-hot masked
            # psum over the *whole* axis (all mesh axes — works on
            # hierarchical meshes too), reduces, and non-members keep
            # their own value.
            k = len(groups[0])
            world = sum(len(g) for g in groups)
            member = np.zeros((world,), bool)
            for r in groups[0]:
                member[r] = True
            my_idx = axis_rank(axis)
            is_member = jnp.asarray(member)[my_idx]
            onehot = jax.nn.one_hot(grank[my_idx], k, dtype=x.dtype)
            contrib = jnp.where(
                is_member,
                onehot.reshape((k,) + (1,) * x.ndim) * x[None],
                jnp.zeros((k,) + x.shape, x.dtype))
            gathered = lax.psum(contrib, axes)
            out = jnp.where(is_member, jnp.prod(gathered, axis=0), x)
    else:  # pragma: no cover
        raise ValueError(op)
    return _apply_scale(out, postscale_factor)


def grouped_allreduce(
    xs: Sequence[jax.Array],
    op: ReduceOp = ReduceOp.SUM,
    axis: AxisSpec = HVD_AXIS,
    process_set=None,
    prescale_factor: Optional[float] = None,
    postscale_factor: Optional[float] = None,
) -> List[jax.Array]:
    """Grouped allreduce: all tensors reduced as one logical op
    (ref EnqueueTensorAllreduces operations.cc:1404, GroupTable group_table.h).

    TPU-native fusion: flatten + concat per dtype into one buffer, one psum per
    dtype, split back — the in-graph analogue of the 128 MiB fusion buffer
    (ref fusion_buffer_manager.h:31). XLA further fuses the pack/unpack copies.
    """
    from horovod_tpu.ops.fusion import fuse_apply
    fn = functools.partial(
        allreduce, op=op, axis=axis, process_set=process_set,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor)
    return fuse_apply(fn, xs)


def allgather(
    x: jax.Array,
    axis: AxisSpec = HVD_AXIS,
    process_set=None,
) -> jax.Array:
    """Concatenate each chip's tensor along dim 0
    (ref AllgatherOp collective_operations.h:137, NCCLAllgather
    nccl_operations.cc:981). Shard shapes must match; the eager layer provides
    the uneven-first-dim (allgatherv) path via pad-to-max.

    Subgroup (process-set) gathers lower to ONE XLA all-gather with
    ``axis_index_groups`` when the registered sets form a size-uniform
    partition of the world (ref per-set communicators
    nccl_operations.cc:981) — each chip receives its own set's gather;
    ragged sets use the eager layer's host-mediated path.

    HOROVOD_HIERARCHICAL_ALLGATHER on a multi-axis (cross, local) mesh
    gathers level by level — innermost (fastest ICI) axis first, then
    outward (ref MPIHierarchicalAllgather mpi_operations.cc:224, node-leader
    two-phase gather); result ordering equals the flat single-shot gather."""
    groups = _uniform_partition_groups(process_set, "allgather")
    axes = _axes_tuple(axis)
    from horovod_tpu.config import knobs
    if groups is None and len(axes) > 1 \
            and knobs.get("HOROVOD_HIERARCHICAL_ALLGATHER"):
        out = x
        for ax in reversed(axes):
            out = lax.all_gather(out, ax, axis=0, tiled=True)
        return out
    return lax.all_gather(x, axes, axis=0, tiled=True,
                          axis_index_groups=groups)


def _uniform_partition_groups(process_set, opname: str):
    """axis_index_groups for a shape-changing subgroup collective, or None
    for the global set (ref per-set communicators nccl_operations.cc:981,
    1156, 1226).

    XLA's replica groups must be size-uniform for shape-changing ops, so a
    subgroup lowers to ONE collective exactly when the world splits into
    equal groups. Resolution order:

    1. Registered sibling partition: if the registered process sets
       include a family of disjoint equal-size sets (this one among them)
       covering the world, use it — each chip receives ITS OWN set's
       result, which is precisely the EP/TP partition semantics (e.g. the
       even/odd sets of examples/moe_alltoall.py).
    2. Aligned contiguous set (ranks [g*k, ..., (g+1)*k - 1]): partition
       the world into contiguous k-chunks. Other chips get their chunk's
       result (their implied sibling set).

    Ragged or unalignable sets raise NotImplementedError — those route
    through the eager layer's host-mediated path, which has no uniformity
    requirement."""
    if process_set is None or process_set.process_set_id == 0:
        return None
    process_set._check_registered()
    table = process_set._table
    world = table.world_size
    k = len(process_set.ranks)
    if k and world % k == 0:
        siblings = [s for s in table.all_sets()
                    if s.process_set_id != 0 and s.ranks
                    and len(s.ranks) == k]
        # Seed the cover with THIS set: the greedy disjoint walk must
        # build the family around the querying set, not whichever
        # equal-size family happens to be registered first (e.g. with
        # both a contiguous-halves and an even/odd partition registered,
        # an even/odd member must resolve to the even/odd family).
        cover: List[List[int]] = [list(process_set.ranks)]
        seen: set = set(process_set.ranks)
        for s in siblings:
            if not seen.intersection(s.ranks):
                cover.append(list(s.ranks))
                seen.update(s.ranks)
        if len(seen) == world:
            return sorted(cover)
        ranks = list(process_set.ranks)
        if ranks == list(range(ranks[0], ranks[0] + k)) \
                and ranks[0] % k == 0:
            return [list(range(g * k, (g + 1) * k))
                    for g in range(world // k)]
    raise NotImplementedError(
        f"in-jit {opname} over process set {process_set.ranks} cannot "
        f"lower to a single XLA collective: replica groups must be "
        f"size-uniform, and neither the registered sets nor contiguous "
        f"alignment partition the {world}-chip world into groups of "
        f"{k}. Use horovod_tpu.eager.{opname}(..., process_set=...) "
        f"(host-mediated) instead, or register a full sibling partition.")


def broadcast(
    x: jax.Array,
    root_rank: int = 0,
    axis: AxisSpec = HVD_AXIS,
    process_set=None,
) -> jax.Array:
    """Every chip receives root's value (ref NCCLBroadcast; MPIBroadcast
    mpi_operations.cc:401). Lowered as a masked psum — the standard SPMD
    broadcast idiom XLA pattern-matches to a collective-broadcast; root_rank is
    the index *within the process set* (ref mpi_ops.py broadcast docs)."""
    groups, _, grank = _resolve_groups(process_set, axis)
    if groups is None:
        idx = axis_rank(axis)
        mask = (idx == root_rank)
        zeros = jnp.zeros_like(x)
        return lax.psum(jnp.where(mask, x, zeros), _axes_tuple(axis))
    axes = _axes_tuple(axis)
    world = sum(len(g) for g in groups)
    member = np.zeros((world,), bool)
    for r in groups[0]:
        member[r] = True
    my_idx = axis_rank(axis)
    is_member = jnp.asarray(member)[my_idx]
    # Members keep only the root's contribution; non-members (singleton
    # groups) broadcast to themselves, i.e. keep their own value.
    mask = jnp.where(is_member, grank[my_idx] == root_rank, True)
    return lax.psum(jnp.where(mask, x, jnp.zeros_like(x)), axes,
                    axis_index_groups=groups)


def alltoall(
    x: jax.Array,
    axis: AxisSpec = HVD_AXIS,
    process_set=None,
) -> jax.Array:
    """Even all-to-all: dim 0 is split into axis_size equal chunks, chunk i goes
    to chip i (ref NCCLAlltoall nccl_operations.cc:1156 grouped send/recv; here
    a single XLA AllToAll on ICI). Uneven splits ("alltoallv",
    ref PrepareOutputAndParams collective_operations.h:199) are provided by
    the eager layer; subgroup process sets lower in-jit with
    ``axis_index_groups`` when the registered sets form a size-uniform
    partition (ref NCCLAlltoall per-set communicator :1156) — each chip
    exchanges within its own set."""
    groups = _uniform_partition_groups(process_set, "alltoall")
    axes = _axes_tuple(axis)
    n = len(groups[0]) if groups is not None else axis_size(axis)
    if x.shape[0] % n != 0:
        raise ValueError(
            f"alltoall first dim {x.shape[0]} not divisible by group size {n}")
    # Multiple axes linearize row-major (outermost first) — the same flat-rank
    # convention as axis_rank — so this works unchanged on a hierarchical
    # (cross, local) mesh.
    return lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True,
                          axis_index_groups=groups)


def reducescatter(
    x: jax.Array,
    op: ReduceOp = ReduceOp.SUM,
    axis: AxisSpec = HVD_AXIS,
    process_set=None,
    prescale_factor: Optional[float] = None,
    postscale_factor: Optional[float] = None,
) -> jax.Array:
    """Reduce then scatter dim-0 slices (ref ReducescatterOp
    collective_operations.h:282, NCCLReducescatter nccl_operations.cc:1226).
    SUM/AVERAGE lower to a native reduce-scatter (psum_scatter); MIN/MAX/PRODUCT
    (not supported by the reference either) fall back to allreduce+slice.
    Subgroup process sets lower in-jit with ``axis_index_groups`` for
    size-uniform partitions (ref NCCLReducescatter per-set communicator
    :1226); ragged sets are eager-layer only (see allgather note)."""
    op = check_supported(op)
    groups = _uniform_partition_groups(process_set, "reducescatter")
    axes = _axes_tuple(axis)
    x = _apply_scale(x, prescale_factor)
    n = len(groups[0]) if groups is not None else axis_size(axis)
    if x.shape[0] % n != 0:
        raise ValueError(
            f"reducescatter first dim {x.shape[0]} not divisible by {n}")
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        out = lax.psum_scatter(x, axes, scatter_dimension=0, tiled=True,
                               axis_index_groups=groups)
        if op == ReduceOp.AVERAGE:
            out = out / jnp.asarray(n, out.dtype)
    else:
        if groups is not None:
            raise NotImplementedError(
                f"subgroup reducescatter supports SUM/AVERAGE (got {op})")
        full = allreduce(x, op=op, axis=axis)
        chunk = x.shape[0] // n
        out = lax.dynamic_slice_in_dim(full, axis_rank(axis) * chunk, chunk,
                                       axis=0)
    return _apply_scale(out, postscale_factor)


def ppermute(x: jax.Array, perm: Sequence[Tuple[int, int]],
             axis: str = HVD_AXIS) -> jax.Array:
    """Point-to-point permutation over the axis ring — the substrate for
    ring-attention / pipeline neighbour exchange (no reference analogue is
    user-exposed; P2P exists only inside the reference's Adasum/alltoall,
    SURVEY §2.4)."""
    return lax.ppermute(x, axis, perm=list(perm))


def barrier(axis: AxisSpec = HVD_AXIS, process_set=None) -> jax.Array:
    """In-graph barrier: a scalar psum every chip must reach
    (ref BarrierOp collective_operations.h:340). Returns the world/set size so
    callers can data-depend on it."""
    one = jnp.ones((), jnp.int32)
    return allreduce(one, op=ReduceOp.SUM, axis=axis, process_set=process_set)


# -- topology-aware composites ------------------------------------------------

def hierarchical_allreduce(
    x: jax.Array,
    op: ReduceOp = ReduceOp.SUM,
    local_axis: str = "hvd_local",
    cross_axis: str = "hvd_cross",
    dcn_axis: Optional[str] = None,
) -> jax.Array:
    """Two-level allreduce: reduce-scatter over the fast local axis, allreduce
    the shard over the cross axis, allgather back over local — exactly the
    reference's NCCLHierarchicalAllreduce (nccl_operations.h:231) and the
    fork's NCCLTorusAllreduce (nccl_operations.cc:698-812), expressed as mesh
    sub-axis reductions. Requires dim 0 divisible by the local axis size; the
    eager layer pads. Only SUM/AVERAGE (the torus path in the reference is also
    sum-only).

    ``dcn_axis``: on a 3-axis multi-slice mesh, the outermost (DCN) axis
    joins the cross stage — the shard allreduce spans (cross, dcn), so one
    call covers the whole world. For the full DCN-aware tier (per-op
    neutral padding, slow-tier-only wire compression) use
    :func:`two_level_allreduce`."""
    op = check_supported(op)
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("hierarchical/torus allreduce supports SUM/AVERAGE")
    cross_axes = (cross_axis, dcn_axis) if dcn_axis else (cross_axis,)
    shard = lax.psum_scatter(x, local_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, cross_axes)
    out = lax.all_gather(shard, local_axis, axis=0, tiled=True)
    if op == ReduceOp.AVERAGE:
        n = lax.axis_size(local_axis)
        for a in cross_axes:
            n *= lax.axis_size(a)
        out = out / jnp.asarray(n, out.dtype)
    return out


# Fork-specific name parity (HOROVOD_TORUS_ALLREDUCE, launch.py:396-407).
torus_allreduce = hierarchical_allreduce


def two_level_allreduce(
    x: jax.Array,
    op: ReduceOp = ReduceOp.SUM,
    ici_axes: AxisSpec = (CROSS_AXIS, LOCAL_AXIS),
    dcn_axis: str = DCN_AXIS,
    wire_codec=None,
    prescale_factor: Optional[float] = None,
    postscale_factor: Optional[float] = None,
    scope: str = "hvd_tier",
) -> jax.Array:
    """DCN-aware two-level allreduce over dim 0 — the multi-pod form of
    the fork's NCCLTorusAllreduce (nccl_operations.cc:698-812):

    1. **reduce-scatter** over the fast intra-slice ``ici_axes`` (each
       rank ends up owning 1/n_ici of the payload, fully reduced within
       its slice);
    2. **cross-slice allreduce** over ``dcn_axis`` of ONLY the owned
       shard — the slow DCN hop moves 1/n_ici of the bytes a flat
       schedule would, and ``wire_codec`` (compression.WireCodec)
       optionally narrows exactly this stage (per-shard global-amax
       scale pmax'ed over ``dcn_axis``; ICI traffic stays full-width);
    3. **all-gather** back over ``ici_axes``.

    Correct for SUM/AVERAGE/MIN/MAX and for dim-0 sizes not divisible by
    the ICI world: the payload is padded with the op's identity
    (:func:`_join_neutral`) and trimmed after the gather. AVERAGE folds
    its 1/world into the cross-stage epilogue (the codec decode when
    compressing). MIN/MAX have no native reduce-scatter, so stage 1 is
    reduce+own-shard-slice — same wire structure, and the codec is
    ignored (a wire SUM of min/max-quantized values has no meaning).

    ``scope`` prefixes the three stage named_scopes (``<scope>_rs`` /
    ``<scope>_xdcn`` / ``<scope>_ag``) that survive into HLO op_name
    metadata — the fused bucket path passes ``hvd_bucket<k>`` so the
    device-profile attribution splits each bucket's time per tier.
    """
    op = check_supported(op)
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN,
                  ReduceOp.MAX):
        raise ValueError(
            f"two_level_allreduce supports SUM/AVERAGE/MIN/MAX, got {op}")
    ici = tuple(a for a in _axes_tuple(ici_axes) if a)
    if not ici:
        raise ValueError("two_level_allreduce needs >= 1 ICI axis")
    n_ici = axis_size(ici)
    n_dcn = lax.axis_size(dcn_axis)
    world = n_ici * n_dcn
    x = _apply_scale(x, prescale_factor)
    orig = x.shape[0]
    pad = (-orig) % n_ici
    if pad:
        fill = jnp.full((pad,) + x.shape[1:], _join_neutral(op, x.dtype),
                        x.dtype)
        x = jnp.concatenate([x, fill])

    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        with jax.named_scope(f"{scope}_rs"):
            shard = lax.psum_scatter(x, ici, scatter_dimension=0,
                                     tiled=True)
        with jax.named_scope(f"{scope}_xdcn"):
            if wire_codec is not None and wire_codec.compresses(x.dtype):
                wire, scale = wire_codec.encode(shard, axes=(dcn_axis,),
                                                world=n_dcn)
                red = lax.psum(wire, dcn_axis)
                post = (1.0 / world) if op == ReduceOp.AVERAGE else None
                shard = wire_codec.decode(red, scale, x.dtype,
                                          postscale=post)
            else:
                shard = lax.psum(shard, dcn_axis)
                if op == ReduceOp.AVERAGE:
                    shard = shard / jnp.asarray(world, shard.dtype)
    else:
        reduce = lax.pmin if op == ReduceOp.MIN else lax.pmax
        with jax.named_scope(f"{scope}_rs"):
            full = reduce(x, ici)
            chunk = x.shape[0] // n_ici
            shard = lax.dynamic_slice_in_dim(
                full, axis_rank(ici) * chunk, chunk, axis=0)
        with jax.named_scope(f"{scope}_xdcn"):
            shard = reduce(shard, dcn_axis)

    with jax.named_scope(f"{scope}_ag"):
        out = lax.all_gather(shard, ici, axis=0, tiled=True)
    if pad:
        out = out[:orig]
    return _apply_scale(out, postscale_factor)
