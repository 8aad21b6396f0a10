"""Adasum: scale-invariant gradient combination.

Reference parity: the templated ``Adasum<Communicator>`` VHDD
(vector-halving distance-doubling) algorithm (reference: common/ops/adasum/
adasum.h:38,194 — pairwise combine a' = (1 − a·b/2|a|²)·a + (1 − a·b/2|b|²)·b
recursively over power-of-2 partner distances; AdasumMPIAllreduceOp
adasum_mpi_operations.cc:30; GPU hierarchical variant adasum_gpu_operations.cc:44).

TPU-native design: the recursive pairwise exchange maps onto ``lax.ppermute``
with XOR-partner permutations at distances 1, 2, 4, … (the hypercube butterfly).
Rather than literally halving vectors and doubling distance (an MPI bandwidth
optimization for point-to-point links), each level exchanges the full working
vector over ICI and both partners compute the symmetric combination — same
numerics, one collective per level, and XLA overlaps the permute with the dot
products of the previous level. Like the reference's MPI path, the world size
must be a power of two.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.runtime.topology import HVD_AXIS


def _pairwise_adasum(a: jax.Array, b: jax.Array) -> jax.Array:
    """a' = (1 − a·b / 2|a|²) a + (1 − a·b / 2|b|²) b  (ref adasum.h:38 doc).

    Orthogonal gradients add; parallel gradients average — interpolating
    between SGD-sum and model averaging without a scale hyperparameter.
    """
    compute_dtype = jnp.promote_types(a.dtype, jnp.float32)
    af = a.astype(compute_dtype).ravel()
    bf = b.astype(compute_dtype).ravel()
    dot = jnp.dot(af, bf)
    na = jnp.dot(af, af)
    nb = jnp.dot(bf, bf)
    # Guard zero norms (reference guards with if-nonzero, adasum.h:420-436).
    ca = jnp.where(na > 0, 1.0 - dot / (2.0 * jnp.where(na > 0, na, 1.0)), 1.0)
    cb = jnp.where(nb > 0, 1.0 - dot / (2.0 * jnp.where(nb > 0, nb, 1.0)), 1.0)
    out = ca.astype(a.dtype) * a + cb.astype(b.dtype) * b
    return out.astype(a.dtype)


def adasum_allreduce(
    x: jax.Array,
    axis: str = HVD_AXIS,
    process_set=None,
    joined_ranks: Tuple[int, ...] = (),
) -> jax.Array:
    """Adasum-reduce x across the axis via a log2(n) XOR butterfly.

    After level k every chip holds the Adasum combination of its 2^(k+1)-chip
    hypercube neighbourhood; after log2(n) levels all chips agree. This is the
    reference's VHDD recursion (adasum.h:194) with full-vector exchange.

    ``joined_ranks`` (static tuple of LINEARIZED ranks, row-major over the
    axes — the convention of ops.collectives): ranks whose contribution the
    caller already zeroed (ref JoinOp collective_operations.h:312). On the
    flat butterfly zero is Adasum's identity (the pairwise zero-norm guard),
    so the list only matters on hierarchical (cross, local) meshes: the
    local averaging must divide by each local group's ACTIVE count, not the
    full group size — otherwise a joined rank dilutes its local group's
    gradient (ref controller.cc:269-327 joined_size accounting).
    """
    if process_set is not None and process_set.process_set_id != 0:
        raise NotImplementedError(
            "Adasum over non-global process sets is not supported "
            "(the reference's MPI Adasum also requires the global comm)")
    if isinstance(axis, (tuple, list)):
        if len(axis) == 1:
            axis = axis[0]
        elif len(axis) == 2:
            # Hierarchical composition (ref AdasumGpuAllreduceOp,
            # adasum_gpu_operations.cc:44-66: local reduce+scale inside
            # the node, VHDD across nodes, broadcast back): average over
            # the fast local axis — any size — then butterfly-Adasum over
            # the cross axis, which alone must be a power of two. Lifts
            # the MPI path's all-world pow2 restriction to
            # local x (pow2 cross) worlds (e.g. 3x2 = 6 chips).
            cross_axis, local_axis = axis
            nc = lax.axis_size(cross_axis)
            if nc & (nc - 1) != 0:
                raise ValueError(
                    f"hierarchical Adasum requires a power-of-2 CROSS axis, "
                    f"got {nc} (ref adasum_gpu_operations.cc:44-66)")
            if joined_ranks:
                # Divide each local group by its ACTIVE member count, not
                # the full group size: joined ranks contribute zeros, and a
                # plain pmean would dilute their group's average (the join
                # x Adasum dilution bug — each group's mean must be over
                # the ranks that actually supplied data). Ranks linearize
                # row-major (cross, local), so rank r belongs to local
                # group r // n_local.
                nl = lax.axis_size(local_axis)
                counts = np.full((nc,), nl, np.int64)
                for r in joined_ranks:
                    g = int(r) // nl
                    if 0 <= g < nc:
                        counts[g] -= 1
                counts = np.maximum(counts, 1)   # all-joined group: zeros
                denom = jnp.asarray(counts)[lax.axis_index(cross_axis)]
                out = lax.psum(x, local_axis) / denom.astype(x.dtype)
            else:
                out = lax.pmean(x, local_axis)
            d = 1
            while d < nc:
                perm = [(r, r ^ d) for r in range(nc)]
                partner = lax.ppermute(out, cross_axis, perm=perm)
                out = _pairwise_adasum(out, partner)
                d *= 2
            return out
        else:
            raise ValueError("adasum_allreduce takes one mesh axis or a "
                             "(cross, local) pair")
    n = lax.axis_size(axis)
    if n & (n - 1) != 0:
        raise ValueError(
            f"Adasum requires a power-of-2 world size, got {n} "
            "(reference MPI path shares the restriction on flat worlds; "
            "hierarchical meshes lift it — pass (cross, local) axes)")
    out = x
    d = 1
    while d < n:
        perm = [(r, r ^ d) for r in range(n)]
        partner = lax.ppermute(out, axis, perm=perm)
        out = _pairwise_adasum(out, partner)
        d *= 2
    return out
