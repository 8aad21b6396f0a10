"""DistributedOptimizer / distributed gradients — the reference's core API.

Reference parity:
- ``hvd.DistributedOptimizer`` (torch/optimizer.py:36 `_DistributedOptimizer`,
  tensorflow/__init__.py:832): wraps an optimizer so gradients are averaged
  across workers before the update, with optional fp16 compression
  (compression.py), gradient accumulation (``backward_passes_per_step``,
  gradient_aggregation.py), process-set scoping, and an Adasum mode
  (torch/optimizer.py:345).
- ``hvd.DistributedGradientTape`` (tensorflow/__init__.py:1051) →
  ``distributed_value_and_grad``.
- ``PartialDistributedGradientTape`` (tensorflow/__init__.py:1130, register
  local vars excluded from sync) → the ``local_param_filter`` argument.

TPU-native form: an ``optax.GradientTransformation`` — the idiomatic JAX
optimizer-wrapping point, exactly where Horovod hooks torch/tf optimizers.
Two sync modes:

- **auto (axis=None)**: no explicit collective. Under ``jit`` with params
  replicated and the batch sharded over the mesh, XLA already inserts one
  fused gradient all-reduce — the compiler does what Horovod's background
  thread, fusion buffer, and cycle loop do by hand. The transform still
  applies compression/averaging semantics.
- **explicit (axis="...")**: inside shard_map/pmap, psum/pmean each gradient
  leaf over the named axis (optionally per-leaf ``sync_axes`` for multi-axis
  meshes, see models/transformer.grad_sync_axes).

**Wire compression** (docs/compression.md): when a wire tier is active
(``HOROVOD_GRADIENT_COMPRESSION`` or a ``compression=`` argument), the
explicit-axis fused path packs each reverse-backward bucket, casts the
packed buffer to the wire dtype (per-bucket global-amax scale for fp8),
runs ONE SUM collective per bucket in the wire dtype, and decompresses in
the epilogue — the reduction itself moves 2-4x fewer bytes. Lossy low-bit
tiers carry an error-feedback residual in the transform state so the
quantization error of step t re-enters step t+1's gradient (convergence:
Karimireddy et al. 2019); the residual is per-rank state with a leading
world-sized dim sharded over the sync axes, so it lives in the
checkpointed TrainState and kill->resume stays bitwise-identical.

**Optimizer-in-epilogue bucketed apply** (:func:`distributed_apply`): the
classic chain decompress -> unflatten -> whole-model optax pass reads and
writes every parameter one extra time. ``DistributedApply`` applies the
optimizer update per bucket inside the decompress epilogue (reverse-
backward bucket order already matches parameter layout), so XLA fuses
decode + momentum update + parameter write into the bucket's epilogue and
no separate whole-model elementwise pass remains — the unfused optax path
stays available as the reference twin (its apply is tagged
``hvd_unfused_apply`` in HLO metadata; equivalence is asserted in tests).
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from jax import lax

from horovod_tpu import compression as compr
from horovod_tpu.compression import Compression
from horovod_tpu.ops.reduce_ops import ReduceOp, check_supported


def _sync_leaf(g, axes, op: ReduceOp, compression) -> Any:
    from horovod_tpu.ops import collectives as C
    compression = compr.as_compressor(compression)  # tier strings OK
    compressed, ctx = compression.compress(g)
    for ax in axes:
        # full reduce-op dispatch (SUM/AVERAGE/MIN/MAX/PRODUCT/ADASUM)
        compressed = C.allreduce(compressed, op=op, axis=ax)
    return compression.decompress(compressed, ctx)


def _bucket_reverse_order(leaves, bucket_bytes: int):
    """Contiguous buckets over the leaf list in REVERSE order, each at most
    ``bucket_bytes`` (every bucket holds at least one leaf). Backward
    produces the LAST parameters' gradients first, and flattened flax/optax
    trees follow forward definition order — so reversed contiguous chunks
    group gradients that become available at similar times, letting each
    bucket's collective start as soon as its own chunk of backward is done
    (the reference's per-parameter async hooks, torch/optimizer.py:167-174,
    as compiler-visible dataflow).

    The plan itself lives in ops/fusion._plan_buckets_by_bytes so the
    expected-collectives manifest (fusion.expected_manifest, checked by
    the HVD502 IR verifier) is derived from the SAME schedule this
    trace produces."""
    from horovod_tpu.ops.fusion import _plan_buckets_by_bytes
    sizes = []
    for g in leaves:
        x = jnp.asarray(g)
        sizes.append(int(x.size) * x.dtype.itemsize)
    return _plan_buckets_by_bytes(sizes, bucket_bytes)


# ---------------------------------------------------------------------------
# wire-bytes trace accounting (hvd_grad_wire_bytes_total /
# hvd_grad_compression_ratio — docs/observability.md). The fused sync runs
# ONCE at trace time; the per-trace static byte counts are recorded here
# and the train loop charges them per executed step
# (record_step_wire_metrics).
# ---------------------------------------------------------------------------

_WIRE_TRACE = {"tier": "none", "logical_bytes": 0, "wire_bytes": 0,
               "n_buckets": 0, "error_feedback": False,
               "schedule": "flat", "dcn_wire_bytes": 0}


def last_wire_trace() -> dict:
    """Static byte accounting of the most recent fused gradient-sync
    trace: wire tier, logical (uncompressed) vs wire bytes per step, the
    bucket count, the DCN schedule (flat | two_level), and — under the
    two-level tier — the bytes that actually crossed the slow DCN hop
    (post compression) — what bench.py's runtime_metrics and the goodput
    ledger record."""
    return dict(_WIRE_TRACE)


def _record_wire_trace(tier: str, logical: int, wire: int, n_buckets: int,
                       ef: bool, schedule: str = "flat",
                       dcn_wire: int = 0) -> None:
    _WIRE_TRACE.update(tier=tier, logical_bytes=int(logical),
                       wire_bytes=int(wire), n_buckets=int(n_buckets),
                       error_feedback=bool(ef), schedule=str(schedule),
                       dcn_wire_bytes=int(dcn_wire))
    from horovod_tpu import metrics as M
    M.gauge("hvd_grad_compression_ratio",
            "Logical/wire byte ratio of the most recent fused gradient-"
            "sync trace (1.0 = uncompressed wire)",
            aggregation="leader").set(
                float(logical) / float(wire) if wire else 1.0)


def record_step_wire_metrics() -> None:
    """Charge one step's gradient wire traffic to the cumulative
    counters (called per step by trainer.train_loop; the eager
    coordinator charges its own bins at dispatch time, exactly).

    The in-graph charge is an ESTIMATE from the most recent fused-sync
    trace: the collectives live inside the compiled step, so the host
    cannot observe per-execution byte counts. It is exact for the
    common one-model steady state; it overcounts when the sync does not
    run every step (optax.MultiSteps accumulation) and attributes to
    the last-traced program when several models trace in one process —
    the hvd_grad_compression_ratio gauge and the ledger 'wire' block
    carry the same per-trace provenance (docs/compression.md)."""
    if not _WIRE_TRACE["logical_bytes"]:
        return
    from horovod_tpu import metrics as M
    M.counter("hvd_grad_wire_bytes_total",
              "Gradient bytes actually moved by the sync collectives "
              "(post wire compression)").inc(_WIRE_TRACE["wire_bytes"])
    M.counter("hvd_grad_logical_bytes_total",
              "Gradient bytes the sync collectives would move "
              "uncompressed").inc(_WIRE_TRACE["logical_bytes"])


def _leaf_nbytes(x) -> int:
    x = jnp.asarray(x)
    return int(x.size) * x.dtype.itemsize


def _tier_split(axes) -> Tuple[Tuple[str, ...], Optional[str]]:
    """``(ici_axes, dcn_axis)`` for one sync-axes tuple: the DCN axis is
    peeled off when the tuple crosses it AND at least one fast (ICI) axis
    remains to reduce-scatter over; otherwise the whole tuple is ICI and
    there is no tier."""
    from horovod_tpu.runtime.topology import DCN_AXIS
    axes = tuple(a for a in axes if a)
    if DCN_AXIS in axes and len(axes) > 1:
        return tuple(a for a in axes if a != DCN_AXIS), DCN_AXIS
    return axes, None


def _wire_bucket_reduce(leaves, res_leaves, axes, op: ReduceOp, world: int,
                        codec, tier=None, scope: str = "hvd_bucket"):
    """One bucket's pack -> (error-feedback compensate) -> encode ->
    SUM collective in the wire dtype -> decode epilogue -> unpack.

    Returns ``(synced_leaves, new_res_leaves, chain_tokens, wire_bytes,
    dcn_wire_bytes)`` where ``chain_tokens`` are the raw collective
    results (the optimization-barrier handles that keep XLA's all-reduce
    combiner from re-merging buckets) and ``new_res_leaves`` is None when
    ``res_leaves`` is. Non-compressible dtypes in the bucket (ints,
    already-narrow floats) reduce uncompressed in the same fused program.

    ``scope`` labels the bucket's ops with a named_scope that survives
    into HLO op_name metadata (the profile-attribution handle).

    ``tier=(ici_axes, dcn_axis)`` switches the bucket to the DCN-aware
    two-level schedule (HOROVOD_DCN_SCHEDULE=two_level; the fork's
    NCCLTorusAllreduce blueprint): intra-slice reduce-scatter over the
    fast ICI axes -> cross-slice SUM over only the owned shard, with the
    wire codec (and the error-feedback residual) applied to EXACTLY this
    slow stage -> intra-slice all-gather. The three stages carry
    ``<scope>_rs`` / ``<scope>_xdcn`` / ``<scope>_ag`` scopes so the
    device-profile attribution splits time per tier. The per-rank
    error-feedback residual then holds this rank's DCN-stage
    quantization error at its own shard offset (zeros elsewhere), so the
    state keeps the gradient leaves' shapes and rides the checkpointed
    TrainState unchanged."""
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops.fusion import flatten_for_fusion, \
        unflatten_from_fusion

    ef = res_leaves is not None
    n = len(leaves)
    outs: List[Any] = [None] * n
    new_res: Optional[List[Any]] = [None] * n if ef else None
    tokens: List[Any] = []
    wire_bytes = 0
    dcn_bytes = 0

    by_dtype = {}
    for i, x in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(x).dtype, []).append(i)
    for dtype, idxs in by_dtype.items():
        buf, specs = flatten_for_fusion([leaves[i] for i in idxs])
        compressed = codec is not None and codec.compresses(buf.dtype)

        if tier is not None:
            ici_axes, dcn_axis = tier
            n_ici = _axes_world(ici_axes)
            n_dcn = _axes_world((dcn_axis,))
            orig = buf.shape[0]
            pad = (-orig) % n_ici
            chunk = (orig + pad) // n_ici
            # payload convention (matches the flat accounting): bytes
            # each collective's result carries — RS + AG move the full
            # bucket on ICI, the DCN stage only the (wire) shard.
            stage = chunk * codec.wire_itemsize \
                + (4 if codec.scaled else 0) if compressed \
                else chunk * buf.dtype.itemsize
            dcn_bytes += stage
            wire_bytes += 2 * orig * buf.dtype.itemsize + stage
            if not (ef and compressed):
                # lossless (or no residual carried): one source of truth
                # for the three-stage schedule — the primitive itself.
                full = C.two_level_allreduce(
                    buf, op=op, ici_axes=ici_axes, dcn_axis=dcn_axis,
                    wire_codec=codec if compressed else None,
                    scope=scope)
                tokens.append(full)
                for slot, o in zip(idxs,
                                   unflatten_from_fusion(full, specs)):
                    outs[slot] = o
                if ef:
                    for slot in idxs:       # lossless: nothing lost
                        new_res[slot] = jnp.zeros_like(
                            jnp.asarray(leaves[slot]))
                continue
            # error feedback: the residual compensates the DCN-stage
            # quantization, so the stages are inlined around the
            # mid-pipeline shard access (same schedule as the primitive).
            if pad:
                buf = jnp.concatenate(
                    [buf, jnp.zeros((pad,), buf.dtype)])
            with jax.named_scope(f"{scope}_rs"):
                shard = lax.psum_scatter(buf, ici_axes,
                                         scatter_dimension=0, tiled=True)
            my_off = C.axis_rank(ici_axes) * chunk
            with jax.named_scope(f"{scope}_xdcn"):
                # each rank stored ITS shard's error at its own offset
                # last step — slice it back out and compensate.
                rbuf, _ = flatten_for_fusion(
                    [jnp.asarray(res_leaves[i]).astype(buf.dtype)
                     for i in idxs])
                if pad:
                    rbuf = jnp.concatenate(
                        [rbuf, jnp.zeros((pad,), rbuf.dtype)])
                shard = shard + lax.dynamic_slice_in_dim(
                    rbuf, my_off, chunk, axis=0)
                wire, scale = codec.encode(shard, axes=(dcn_axis,),
                                           world=n_dcn)
                red = C.allreduce(wire, op=ReduceOp.SUM, axis=dcn_axis)
                post = (1.0 / world) if (op == ReduceOp.AVERAGE
                                         and world != 1) else None
                out_shard = codec.decode(red, scale, buf.dtype,
                                         postscale=post)
                res_shard = shard - codec.decode(wire, scale, buf.dtype)
            with jax.named_scope(f"{scope}_ag"):
                full = lax.all_gather(out_shard, ici_axes, axis=0,
                                      tiled=True)
            if pad:
                full = full[:orig]
            tokens.append(full)
            for slot, o in zip(idxs, unflatten_from_fusion(full, specs)):
                outs[slot] = o
            res_full = jnp.zeros((orig + pad,), buf.dtype)
            res_full = lax.dynamic_update_slice_in_dim(
                res_full, res_shard, my_off, axis=0)
            if pad:
                res_full = res_full[:orig]
            for slot, r in zip(idxs,
                               unflatten_from_fusion(res_full, specs)):
                new_res[slot] = r
            continue

        with jax.named_scope(scope):
            if ef and compressed:
                rbuf, _ = flatten_for_fusion(
                    [jnp.asarray(res_leaves[i]).astype(buf.dtype)
                     for i in idxs])
                buf = buf + rbuf
            if compressed:
                wire, scale = codec.encode(buf, axes=axes, world=world)
                red = wire
                for ax in axes:
                    red = C.allreduce(red, op=ReduceOp.SUM, axis=ax)
                post = (1.0 / world) if (op == ReduceOp.AVERAGE
                                         and world != 1) else None
                out = codec.decode(red, scale, buf.dtype, postscale=post)
                if ef:
                    # residual = compensated gradient minus what this
                    # rank's quantization actually contributed to the
                    # wire sum — the SAME global scale decodes both
                    # sides.
                    res_buf = buf - codec.decode(wire, scale, buf.dtype)
                wire_bytes += wire.size * codec.wire_itemsize \
                    + (4 if codec.scaled else 0)
            else:
                red = buf
                for ax in axes:
                    red = C.allreduce(red, op=op, axis=ax)
                out = red
                if ef:
                    res_buf = jnp.zeros_like(buf)  # lossless: nothing lost
                wire_bytes += buf.size * buf.dtype.itemsize
            tokens.append(red)
            for slot, o in zip(idxs, unflatten_from_fusion(out, specs)):
                outs[slot] = o
            if ef:
                for slot, r in zip(idxs,
                                   unflatten_from_fusion(res_buf, specs)):
                    new_res[slot] = r
    return outs, new_res, tuple(tokens), wire_bytes, dcn_bytes


def _plan_sync_buckets(gs, axes, world: int):
    """The bucket schedule for one fused sync: resolve the bucket knob
    for this (payload, world) and chunk the leaf list in reverse backward
    order — 0/one-leaf payloads collapse to a single bucket."""
    from horovod_tpu.autotune import resolve_bucket_bytes
    bucket_bytes = resolve_bucket_bytes(
        [(jnp.shape(g), jnp.asarray(g).dtype) for g in gs], world)
    if bucket_bytes <= 0 or len(gs) <= 1:
        return [list(range(len(gs)))]
    return _bucket_reverse_order(gs, bucket_bytes)


def _axes_world(axes) -> int:
    """Total rank count across the named axes, INSIDE a traced mesh
    context."""
    world = 1
    for ax in axes:
        world *= int(lax.axis_size(ax))
    return world


def _resolve_tier(gs, axes, op: ReduceOp
                  ) -> Optional[Tuple[Tuple[str, ...], str]]:
    """``(ici_axes, dcn_axis)`` when this sync should run the two-level
    DCN schedule, else None: the axes must cross the DCN axis with at
    least one ICI axis left, the op must be SUM/AVERAGE (the tier's
    cross stage is a wire SUM), and HOROVOD_DCN_SCHEDULE must resolve
    two_level for this payload (autotune.resolve_dcn_schedule — 'auto'
    scores the ICI-vs-DCN latency/bandwidth model)."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        return None
    ici_axes, dcn_axis = _tier_split(axes)
    if dcn_axis is None or not ici_axes:
        return None
    from horovod_tpu.autotune import resolve_dcn_schedule
    payload = sum(_leaf_nbytes(g) for g in gs)
    if resolve_dcn_schedule(payload, _axes_world(ici_axes),
                            _axes_world((dcn_axis,))) != "two_level":
        return None
    return ici_axes, dcn_axis


def _sync_leaves_fused(gs, axes, op: ReduceOp, compression,
                       residuals=None):
    """Sync many gradient leaves as a small number of bucketed fused
    collectives — the in-graph fusion buffer (ref
    fusion_buffer_manager.h:31-47 / FuseResponses controller.cc:887) plus
    the reference's comm/compute overlap (operations.cc:383-402: allreduce
    of layer N's gradient overlaps backward of layers N-1…1).

    Gradients are packed into contiguous buckets of at most
    HOROVOD_GRADIENT_BUCKET_BYTES in reverse backward order; each bucket
    becomes one all-reduce per dtype whose data dependence covers only its
    own leaves, so XLA's latency-hiding scheduler starts late-layer
    buckets' collectives while earlier layers' backward is still running.
    Bucket bytes 0 restores the single-fused-buffer behavior (a ResNet-50
    step = ~2 all-reduces, zero overlap). ADASUM is excluded (its dot
    products are per-tensor; a concatenated buffer would change the
    combination) and falls back to per-leaf sync.

    When a wire tier is active (compression.active_wire_tier — the
    HOROVOD_GRADIENT_COMPRESSION knob or the compression= argument), each
    packed bucket is cast to the wire dtype before its collective and
    decompressed in the epilogue (the wire path always packs: the pack IS
    the bucket, so HOROVOD_BATCH_D2D_MEMCOPIES does not apply). Pass
    ``residuals`` (per-leaf error-feedback state, same shapes as ``gs``)
    to get ``(synced, new_residuals)`` back instead of just the synced
    list; only SUM/AVERAGE ops compress — anything else falls back to the
    uncompressed wire."""
    from horovod_tpu.config import knobs
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops.fusion import fuse_apply

    def with_res(synced):
        return (synced, residuals) if residuals is not None else synced

    if op == ReduceOp.ADASUM:
        # per-leaf sync, uncompressed wire — recorded so a caller
        # accumulating last_wire_trace() per group never reads a STALE
        # trace from some earlier program
        logical = sum(_leaf_nbytes(g) for g in gs)
        _record_wire_trace("none", logical, logical, len(gs), False)
        return with_res([_sync_leaf(g, axes, op, compression) for g in gs])

    codec = compr.wire_codec(compression)
    if codec is not None and op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        codec = None                      # wire sum has no meaning here
    if not tuple(a for a in axes if a):
        # empty-axes (local / fully-sharded) group: no collective runs,
        # so quantizing would cost precision while saving zero wire
        # bytes — same guard DistributedApply.apply applies per group
        codec = None

    world = _axes_world(axes)

    # DCN two-level tier (docs/hierarchical.md): when the sync axes cross
    # the slow outer DCN axis and the schedule resolves two_level, route
    # every bucket through per-slice reduce-scatter -> cross-slice
    # allreduce (the wire codec compresses ONLY this stage) -> intra-
    # slice all-gather. Trace-time decision, like the bucket knob.
    tier = _resolve_tier(gs, axes, op)
    if tier is not None and codec is None \
            and compr.as_compressor(compression) \
            is not compr.NoneCompressor:
        # a duck-typed custom compressor has no wire tier and lives on
        # the per-leaf path (compression.tier_for) — the tier's bucket
        # pipeline would silently drop it, so the flat per-leaf schedule
        # keeps the user's numerics instead
        tier = None

    if codec is None and tier is None:
        # Uncompressed wire: the pre-wire per-leaf compress path (kept as
        # the reference twin the numerics tests pin against). Tier
        # strings normalize to their per-leaf Compressor here.
        compression = compr.as_compressor(compression)
        compressed, ctxs = [], []
        for g in gs:
            c, ctx = compression.compress(g)
            compressed.append(c)
            ctxs.append(ctx)

        def reduce_buf(buf):
            for ax in axes:
                buf = C.allreduce(buf, op=op, axis=ax)
            return buf

        batch = bool(knobs.get("HOROVOD_BATCH_D2D_MEMCOPIES"))
        # 'auto' resolves the AOT sweep cache under (grad shapes, world) —
        # the trace-time analogue of the reference's runtime parameter
        # manager (autotune.resolve_bucket_bytes; cache misses fall back
        # to the default and warn). Also exports the
        # hvd_gradient_bucket_bytes gauge.
        from horovod_tpu.autotune import resolve_bucket_bytes
        bucket_bytes = resolve_bucket_bytes(
            [(jnp.shape(g), jnp.asarray(g).dtype) for g in compressed],
            world)
        logical = sum(_leaf_nbytes(c) for c in compressed)
        if bucket_bytes <= 0 or len(compressed) <= 1:
            # One fused buffer still gets the bucket label: the profile
            # attribution (tracing/profile.bucket_map_from_hlo) maps HLO
            # metadata op_name back to buckets, and the single-buffer case
            # is simply "one bucket".
            n_buckets = 1
            with jax.named_scope("hvd_bucket0"):
                fused = fuse_apply(reduce_buf, compressed, batch=batch)
        else:
            fused = [None] * len(compressed)
            prev = None
            buckets = _bucket_reverse_order(compressed, bucket_bytes)
            n_buckets = len(buckets)
            for k, bucket in enumerate(buckets):
                leaves = [compressed[i] for i in bucket]
                if prev is not None:
                    # Chain buckets through an optimization barrier: a real
                    # dependence edge from EVERY collective result of
                    # bucket k (all dtype groups / per-leaf outputs) to
                    # bucket k+1's pack. Without it XLA's all-reduce
                    # combiner merges buckets back into one collective
                    # (observed on both CPU and TPU pipelines), restoring
                    # the full data dependence on the last gradient and
                    # killing the overlap. With it, buckets serialize among
                    # themselves (they would on the ICI ring anyway) while
                    # each start hoists above the remaining backward
                    # compute — PyTorch DDP's bucket semantics.
                    leaves, _ = lax.optimization_barrier((leaves, prev))
                # Label every op of this bucket's pack/reduce/unpack with a
                # named_scope that survives into HLO metadata op_name — the
                # handle the device-profile attribution uses to credit
                # on-device time to buckets (tracing/profile.py). A
                # host-side trace.span here would be wrong: this body runs
                # ONCE at trace time (hvdlint HVD206).
                with jax.named_scope(f"hvd_bucket{k}"):
                    outs = fuse_apply(reduce_buf, leaves, batch=batch)
                prev = tuple(outs)
                for i, o in zip(bucket, outs):
                    fused[i] = o
        _record_wire_trace("none", logical, logical, n_buckets, False)
        return with_res([compression.decompress(o, ctx)
                         for o, ctx in zip(fused, ctxs)])

    # ---- compressed and/or tiered wire: bucket-level schedule -----------
    n = len(gs)
    buckets = _plan_sync_buckets(gs, axes, world)
    outs: List[Any] = [None] * n
    new_res: Optional[List[Any]] = [None] * n \
        if residuals is not None else None
    prev = None
    wire_total = 0
    dcn_total = 0
    for k, bucket in enumerate(buckets):
        leaves = [gs[i] for i in bucket]
        res = [residuals[i] for i in bucket] \
            if residuals is not None else None
        if prev is not None:
            if res is not None:
                (leaves, res), _ = lax.optimization_barrier(
                    ((leaves, res), prev))
            else:
                leaves, _ = lax.optimization_barrier((leaves, prev))
        bouts, bres, tokens, wb, db = _wire_bucket_reduce(
            leaves, res, axes, op, world, codec, tier=tier,
            scope=f"hvd_bucket{k}")
        prev = tokens
        wire_total += wb
        dcn_total += db
        for slot, o in zip(bucket, bouts):
            outs[slot] = o
        if new_res is not None:
            for slot, r in zip(bucket, bres):
                new_res[slot] = r
    _record_wire_trace(codec.tier if codec is not None else "none",
                       sum(_leaf_nbytes(g) for g in gs),
                       wire_total, len(buckets), residuals is not None,
                       schedule="two_level" if tier is not None
                       else "flat", dcn_wire=dcn_total)
    return (outs, new_res) if residuals is not None else outs


# ---------------------------------------------------------------------------
# error-feedback residual state (optax-transform form)
# ---------------------------------------------------------------------------

class WireState(NamedTuple):
    """Transform state of :func:`allreduce_gradients` when a lossy wire
    tier carries error feedback: ``residual`` mirrors the gradient tree
    with a leading world-sized dim (per-rank state, sharded over the sync
    axes — :func:`wire_state_specs`). Lives inside the optimizer state,
    hence inside the checkpointed TrainState."""
    residual: Any


def _static_axes_world(axes, mesh=None) -> Optional[int]:
    """Rank count across named axes OUTSIDE a traced context: an explicit
    mesh, the active hvd context's topology, or None when neither can
    resolve the axes."""
    sources = []
    if mesh is not None:
        sources.append(mesh)
    try:
        from horovod_tpu.runtime.context import get_context
        sources.append(get_context().topology.mesh)
    except Exception:
        pass
    for m in sources:
        try:
            world = 1
            for ax in axes:
                world *= int(m.shape[ax])
            return world
        except Exception:
            continue
    return None


def _residual_zeros(leaf, world: int):
    x = jnp.asarray(leaf) if not hasattr(leaf, "shape") else leaf
    dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.float32
    return jnp.zeros((max(int(world), 1),) + tuple(x.shape), dtype)


def wire_state_specs(state, axis=None, sync_axes=None):
    """PartitionSpec tree for passing a :class:`WireState`-bearing
    optimizer state through ``shard_map``: residual leaves get their
    leading world dim sharded over the sync axes, everything else is
    replicated. Mirrors the state's tree structure."""
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        names = [getattr(p, "name", None) for p in path]
        if "residual" in names:
            if sync_axes is not None:
                # per-leaf axes would need the sync_axes alignment; the
                # leading dim is sharded over the union tuple, which is
                # correct when all synced leaves share the axes set (the
                # common case this helper serves)
                axes_t = tuple(sorted({a for t in jax.tree_util.tree_leaves(
                    sync_axes, is_leaf=lambda x: isinstance(x, tuple))
                    for a in (t if isinstance(t, tuple) else (t,)) if a}))
            else:
                axes_t = axis if isinstance(axis, tuple) else (axis,)
                axes_t = tuple(a for a in axes_t if a)
            return P(axes_t if len(axes_t) != 1 else axes_t[0])
        return P()

    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    treedef = jax.tree_util.tree_structure(state)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(path, leaf) for path, leaf in flat])


def _squeeze_residual(r, g):
    """Per-shard residual view: a (1, *shape) slice (sharded leading
    world dim) squeezes to the local residual."""
    r = jnp.asarray(r)
    if r.ndim == jnp.ndim(g) + 1 and r.shape[0] == 1 \
            and tuple(r.shape[1:]) == tuple(jnp.shape(g)):
        return jnp.squeeze(r, 0)
    raise ValueError(
        f"error-feedback residual has shape {r.shape} per shard for a "
        f"gradient of shape {jnp.shape(g)} — the residual's leading "
        f"world dim must be sharded over the sync axes inside shard_map "
        f"(pass the state through with hvd.wire_state_specs)")


def allreduce_gradients(
    op: ReduceOp = ReduceOp.AVERAGE,
    axis: Optional[Union[str, tuple]] = None,
    compression: type = Compression.none,
    sync_axes: Any = None,
    local_param_filter: Optional[Callable[[tuple], bool]] = None,
    error_feedback: Optional[bool] = None,
    mesh: Any = None,
) -> optax.GradientTransformation:
    """Gradient-sync transform (the allreduce step of DistributedOptimizer).

    ``sync_axes``: optional pytree (matching the grad tree, leaves =
    tuple-of-axis-names) for per-parameter sync on multi-axis meshes;
    overrides ``axis``. ``local_param_filter(path) -> True`` marks a param
    LOCAL (excluded from sync — ref PartialDistributedGradientTape).

    ``error_feedback``: carry the lossy-wire residual in the transform
    state (default: the HOROVOD_GRADIENT_ERROR_FEEDBACK policy — on for
    fp8 tiers). Needs the mesh axis sizes at ``init`` time (an initialized
    hvd context, or pass ``mesh=``); in explicit-axis mode thread the
    state through shard_map with :func:`wire_state_specs`.
    """
    op = check_supported(op)
    compr.tier_for(compression)   # reject typos HERE, not at trace time

    def _ef_active() -> bool:
        if axis is None and sync_axes is None:
            return False                 # auto mode: precision knob only
        codec = compr.wire_codec(compression)
        if codec is None:
            return False
        return compr.error_feedback_enabled(codec) \
            if error_feedback is None else bool(error_feedback)

    def init_fn(params):
        if not _ef_active() or params is None:
            return optax.EmptyState()
        if sync_axes is not None:
            from horovod_tpu.ops.fusion import group_leaves_by_axes
            treedef, leaves, groups = group_leaves_by_axes(
                params, sync_axes)
            worlds = [1] * len(leaves)
            for axes_t, idxs in groups.items():
                w = _static_axes_world(axes_t, mesh)
                if w is None:
                    _warn_no_mesh()
                    return optax.EmptyState()
                for i in idxs:
                    worlds[i] = w
            res = [_residual_zeros(l, w) for l, w in zip(leaves, worlds)]
            return WireState(jax.tree_util.tree_unflatten(treedef, res))
        axes_t = axis if isinstance(axis, tuple) else (axis,)
        world = _static_axes_world(tuple(a for a in axes_t if a), mesh)
        if world is None:
            _warn_no_mesh()
            return optax.EmptyState()
        return WireState(jax.tree.map(
            lambda l: _residual_zeros(l, world), params))

    def _warn_no_mesh():
        from horovod_tpu.utils.logging import get_logger
        get_logger("horovod_tpu.distributed").warning(
            "wire-compression error feedback requested but the mesh axis "
            "sizes are not resolvable at init time (no initialized hvd "
            "context and no mesh= argument) — continuing WITHOUT the "
            "residual; low-bit compression may bias convergence")

    def update_fn(updates, state, params=None):
        del params
        ef = isinstance(state, WireState)
        res_tree = state.residual if ef else None
        if axis is None and sync_axes is None:
            # auto mode: XLA inserts the cross-replica sum under jit. NOTE:
            # compression here is a *precision* knob only, not a bandwidth
            # saving — the partitioner has already placed the gradient
            # reduction before this transform runs, so the wire transfer
            # keeps the gradient's original dtype; the round-trip merely
            # truncates values to the wire dtype for numerical parity with
            # the explicit-axis path. For real on-the-wire compression use
            # axis=/sync_axes= (explicit collectives compress before the
            # reduce, the bucket wire path above).
            leaf_compr = compr.as_compressor(compression)

            def auto(g):
                c, ctx = leaf_compr.compress(g)
                return leaf_compr.decompress(c, ctx)
            synced = jax.tree.map(auto, updates)
        elif sync_axes is not None:
            # Group leaves by their axes tuple and fuse within each group
            # (one collective per (axes, dtype) — the fusion buffer, with
            # per-parameter axis scoping preserved; coarse sync_axes trees
            # cover whole subtrees).
            from horovod_tpu.ops.fusion import group_leaves_by_axes
            treedef, leaves, groups = group_leaves_by_axes(
                updates, sync_axes)
            res_flat = None
            if ef:
                res_flat = [_squeeze_residual(r, g) for r, g in zip(
                    jax.tree_util.tree_leaves(res_tree), leaves)]
            out = [None] * len(leaves)
            new_res = [None] * len(leaves)
            acct = {"tier": "none", "logical": 0, "wire": 0,
                    "buckets": 0, "schedule": "flat", "dcn": 0}
            for axes_t, idxs in groups.items():
                sub_res = [res_flat[i] for i in idxs] if ef else None
                result = _sync_leaves_fused(
                    [leaves[i] for i in idxs], axes_t, op, compression,
                    residuals=sub_res)
                synced_leaves, sub_new = result if ef else (result, None)
                for i, s in zip(idxs, synced_leaves):
                    out[i] = s
                if ef:
                    for i, r in zip(idxs, sub_new):
                        new_res[i] = r
                if axes_t:
                    # _sync_leaves_fused records per call; accumulate so
                    # ONE update's trace covers every synced group (local
                    # axes-less groups never touch the wire — excluded)
                    g_trace = last_wire_trace()
                    acct["logical"] += g_trace["logical_bytes"]
                    acct["wire"] += g_trace["wire_bytes"]
                    acct["buckets"] += g_trace["n_buckets"]
                    acct["dcn"] += g_trace["dcn_wire_bytes"]
                    if g_trace["tier"] != "none":
                        acct["tier"] = g_trace["tier"]
                    if g_trace["schedule"] != "flat":
                        acct["schedule"] = g_trace["schedule"]
            _record_wire_trace(acct["tier"], acct["logical"],
                               acct["wire"], acct["buckets"], ef,
                               schedule=acct["schedule"],
                               dcn_wire=acct["dcn"])
            synced = jax.tree_util.tree_unflatten(treedef, out)
            if ef:
                res_tree = jax.tree_util.tree_unflatten(
                    treedef, [jnp.expand_dims(r, 0) for r in new_res])
        else:
            axes = axis if isinstance(axis, tuple) else (axis,)
            g_leaves, treedef = jax.tree_util.tree_flatten(updates)
            res_flat = None
            if ef:
                res_flat = [_squeeze_residual(r, g) for r, g in zip(
                    jax.tree_util.tree_leaves(res_tree), g_leaves)]
            result = _sync_leaves_fused(g_leaves, axes, op, compression,
                                        residuals=res_flat)
            synced_leaves, new_res = result if ef else (result, None)
            synced = jax.tree_util.tree_unflatten(treedef, synced_leaves)
            if ef:
                res_tree = jax.tree_util.tree_unflatten(
                    treedef, [jnp.expand_dims(r, 0) for r in new_res])

        if local_param_filter is not None:
            flat_synced = jax.tree_util.tree_flatten_with_path(updates)[0]
            synced_flat = jax.tree.leaves(synced)
            out = []
            for (path, g), s in zip(flat_synced, synced_flat):
                out.append(g if local_param_filter(path) else s)
            treedef = jax.tree.structure(updates)
            synced = jax.tree_util.tree_unflatten(treedef, out)
        return synced, (WireState(res_tree) if ef else state)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis: Optional[Union[str, tuple]] = None,
    compression: type = Compression.none,
    backward_passes_per_step: int = 1,
    sync_axes: Any = None,
    local_param_filter: Optional[Callable[[tuple], bool]] = None,
    error_feedback: Optional[bool] = None,
    mesh: Any = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer with distributed gradient sync
    (ref torch/optimizer.py:560 DistributedOptimizer signature: compression,
    backward_passes_per_step, op, process_set; tensorflow/__init__.py:832).

    ``backward_passes_per_step > 1`` accumulates N microbatch gradients
    locally before one sync + update (ref gradient_aggregation.py
    LocalGradientAggregationHelper) via optax.MultiSteps — communication
    happens once per N steps.

    ``compression`` (or the HOROVOD_GRADIENT_COMPRESSION knob, which
    overrides it) selects the bucket wire tier of the explicit-axis fused
    sync; lossy low-bit tiers carry an error-feedback residual in the
    transform state (see :func:`allreduce_gradients`). The active tier is
    auto-declared in the expected-collectives manifest
    (ops/fusion.expected_manifest), so a compressed step passes
    ``hvd.verify_step`` without hand-written entries.
    """
    chained = optax.chain(
        allreduce_gradients(op=op, axis=axis, compression=compression,
                            sync_axes=sync_axes,
                            local_param_filter=local_param_filter,
                            error_feedback=error_feedback, mesh=mesh),
        optimizer,
    )
    if backward_passes_per_step > 1:
        return optax.MultiSteps(
            chained, every_k_schedule=backward_passes_per_step)
    return chained


# ---------------------------------------------------------------------------
# optimizer-in-epilogue bucketed apply
# ---------------------------------------------------------------------------

class EpilogueOptState(NamedTuple):
    """State of an :class:`EpilogueOptimizer`: ``scalars`` are whole-model
    scalars (e.g. Adam's step count), ``slots`` a tuple of trees mirroring
    the params (momentum, second moment)."""
    scalars: Tuple[Any, ...]
    slots: Tuple[Any, ...]


class DistributedApplyState(NamedTuple):
    """TrainState-resident state of :func:`distributed_apply`: the
    epilogue optimizer's state plus the error-feedback residual tree
    (leading world dim; ``()`` when no residual is carried)."""
    opt: EpilogueOptState
    residual: Any


class EpilogueOptimizer:
    """A leaf-local optimizer whose update can run inside a bucket's
    decompress epilogue: ``apply_leaf`` consumes one parameter leaf, its
    synced gradient, and this leaf's state slots, and returns the NEW
    parameter — so XLA fuses decode + state update + parameter write into
    the bucket's epilogue and no separate whole-model elementwise pass
    remains. Per-step scalar work (step counts, bias corrections) happens
    once in ``begin_step``."""

    n_slots = 0

    def init_scalars(self) -> Tuple[Any, ...]:
        return ()

    def init_slot(self, slot: int, param):
        return jnp.zeros_like(param)

    def begin_step(self, scalars: Tuple[Any, ...]):
        """-> (new_scalars, ctx) — ctx is threaded to every apply_leaf."""
        return scalars, None

    def apply_leaf(self, ctx, param, grad, slots: Tuple[Any, ...]):
        raise NotImplementedError


class EpilogueSGD(EpilogueOptimizer):
    """SGD with optional (Nesterov) momentum — the optax
    ``sgd(lr, momentum, nesterov)`` math, leaf-local."""

    def __init__(self, lr: float, momentum: float = 0.0,
                 nesterov: bool = False):
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.nesterov = bool(nesterov)
        self.n_slots = 1 if self.momentum else 0

    def apply_leaf(self, ctx, param, grad, slots):
        g = grad.astype(param.dtype)
        if not self.momentum:
            return param - self.lr * g, ()
        m = slots[0] * self.momentum + g
        d = g + self.momentum * m if self.nesterov else m
        return param - self.lr * d, (m,)


class EpilogueAdam(EpilogueOptimizer):
    """Adam — the optax ``adam(lr, b1, b2, eps)`` math, leaf-local with a
    shared step-count scalar (bias corrections computed once per step in
    ``begin_step``)."""

    n_slots = 2

    def __init__(self, lr: float, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8):
        self.lr = float(lr)
        self.b1 = float(b1)
        self.b2 = float(b2)
        self.eps = float(eps)

    def init_scalars(self):
        return (jnp.zeros((), jnp.int32),)

    def begin_step(self, scalars):
        count = scalars[0] + 1
        c = count.astype(jnp.float32)
        bc1 = 1.0 - self.b1 ** c
        bc2 = 1.0 - self.b2 ** c
        return (count,), (bc1, bc2)

    def apply_leaf(self, ctx, param, grad, slots):
        bc1, bc2 = ctx
        g = grad.astype(param.dtype)
        mu = self.b1 * slots[0] + (1.0 - self.b1) * g
        nu = self.b2 * slots[1] + (1.0 - self.b2) * (g * g)
        mu_hat = mu / bc1.astype(param.dtype)
        nu_hat = nu / bc2.astype(param.dtype)
        step = self.lr * mu_hat / (jnp.sqrt(nu_hat) + self.eps)
        return param - step, (mu, nu)


class DistributedApply:
    """Fused sync + optimizer-in-epilogue apply (build with
    :func:`distributed_apply`). ``apply(params, grads, state)`` runs
    INSIDE shard_map: per reverse-backward bucket it packs, wire-encodes,
    reduces, decodes, and immediately applies the optimizer update to the
    bucket's leaves under ``hvd_bucket<k>_apply`` — eliminating the
    whole-model optimizer read/write pass of the decompress -> unflatten
    -> optax chain (which remains the reference twin, tagged
    ``hvd_unfused_apply``)."""

    def __init__(self, optimizer: EpilogueOptimizer, *,
                 op: ReduceOp = ReduceOp.AVERAGE,
                 axis: Optional[Union[str, tuple]] = None,
                 sync_axes: Any = None,
                 compression: type = Compression.none,
                 error_feedback: Optional[bool] = None,
                 mesh: Any = None):
        if axis is None and sync_axes is None:
            raise ValueError(
                "DistributedApply needs an explicit mesh axis (axis= or "
                "sync_axes=): the bucketed sync+apply is traced inside "
                "shard_map; auto mode has no bucket epilogue to apply in")
        compr.tier_for(compression)   # reject typos at construction
        self.optimizer = optimizer
        self.op = check_supported(op)
        self.axis = axis
        self.sync_axes = sync_axes
        self.compression = compression
        self.mesh = mesh
        self._ef_override = error_feedback

    # -- static wiring ----------------------------------------------------
    def _codec(self):
        codec = compr.wire_codec(self.compression)
        if codec is not None and self.op not in (ReduceOp.SUM,
                                                 ReduceOp.AVERAGE):
            codec = None
        return codec

    def error_feedback_active(self) -> bool:
        codec = self._codec()
        if codec is None:
            return False
        return compr.error_feedback_enabled(codec) \
            if self._ef_override is None else bool(self._ef_override)

    def _groups(self, tree):
        """(treedef, leaves, {axes_tuple: [leaf indices]})."""
        from horovod_tpu.ops.fusion import group_leaves_by_axes
        if self.sync_axes is not None:
            return group_leaves_by_axes(tree, self.sync_axes)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        axes_t = self.axis if isinstance(self.axis, tuple) else (self.axis,)
        axes_t = tuple(a for a in axes_t if a)
        return treedef, leaves, {axes_t: list(range(len(leaves)))}

    def init(self, params) -> DistributedApplyState:
        opt = self.optimizer
        slots = tuple(
            jax.tree.map(lambda p, s=s: opt.init_slot(s, p), params)
            for s in range(opt.n_slots))
        residual: Any = ()
        if self.error_feedback_active():
            treedef, leaves, groups = self._groups(params)
            worlds = [1] * len(leaves)
            for axes_t, idxs in groups.items():
                w = _static_axes_world(axes_t, self.mesh)
                if w is None:
                    raise ValueError(
                        "DistributedApply error feedback needs the mesh "
                        "axis sizes at init time — pass mesh= or call "
                        "inside an initialized hvd context")
                for i in idxs:
                    worlds[i] = w
            residual = jax.tree_util.tree_unflatten(
                treedef, [_residual_zeros(l, w)
                          for l, w in zip(leaves, worlds)])
        return DistributedApplyState(
            EpilogueOptState(opt.init_scalars(), slots), residual)

    def state_specs(self, param_specs) -> DistributedApplyState:
        """shard_map in/out specs for a :class:`DistributedApplyState`:
        slots mirror the param specs, scalars are replicated, residual
        leaves get their leading world dim sharded over the leaf's sync
        axes with the param's own spec appended."""
        from jax.sharding import PartitionSpec as P
        opt = self.optimizer
        slots = tuple(param_specs for _ in range(opt.n_slots))
        scalars = tuple(P() for _ in opt.init_scalars())
        residual: Any = ()
        if self.error_feedback_active():
            is_p = lambda x: isinstance(x, P)  # noqa: E731
            spec_leaves, treedef = jax.tree_util.tree_flatten(
                param_specs, is_leaf=is_p)
            # align per-leaf sync axes with the spec leaves
            if self.sync_axes is not None:
                from horovod_tpu.ops.fusion import group_leaves_by_axes
                _, _, groups = group_leaves_by_axes(
                    jax.tree_util.tree_unflatten(
                        treedef, list(range(len(spec_leaves)))),
                    self.sync_axes)
                leaf_axes = [()] * len(spec_leaves)
                for axes_t, idxs in groups.items():
                    for i in idxs:
                        leaf_axes[i] = axes_t
            else:
                axes_t = self.axis if isinstance(self.axis, tuple) \
                    else (self.axis,)
                axes_t = tuple(a for a in axes_t if a)
                leaf_axes = [axes_t] * len(spec_leaves)
            res_specs = []
            for spec, axes_t in zip(spec_leaves, leaf_axes):
                lead = axes_t if len(axes_t) != 1 else axes_t[0]
                res_specs.append(P(lead, *tuple(spec)))
            residual = jax.tree_util.tree_unflatten(treedef, res_specs)
        return DistributedApplyState(
            EpilogueOptState(scalars, slots), residual)

    # -- the fused step body ----------------------------------------------
    def apply(self, params, grads, state: DistributedApplyState
              ) -> Tuple[Any, DistributedApplyState]:
        opt = self.optimizer
        codec = self._codec()
        ef = self.error_feedback_active()
        treedef, g_leaves, groups = self._groups(grads)
        p_leaves = jax.tree_util.tree_leaves(params)
        if len(p_leaves) != len(g_leaves):
            raise ValueError(
                f"params tree has {len(p_leaves)} leaves but the gradient "
                f"tree has {len(g_leaves)}")
        slot_leaves = [jax.tree_util.tree_leaves(s)
                       for s in state.opt.slots]
        res_leaves = None
        if ef:
            res_leaves = [
                _squeeze_residual(r, g) for r, g in zip(
                    jax.tree_util.tree_leaves(state.residual), g_leaves)]
        scalars, ctx = opt.begin_step(state.opt.scalars)

        n = len(g_leaves)
        new_p: List[Any] = [None] * n
        new_slots: List[List[Any]] = [[None] * n
                                      for _ in range(opt.n_slots)]
        new_res: List[Any] = [None] * n
        bucket_no = 0
        logical = wire_total = dcn_total = 0
        n_buckets = 0
        schedule = "flat"
        for axes_t, idxs in groups.items():
            world = _axes_world(axes_t) if axes_t else 1
            group_codec = codec if axes_t else None
            group_tier = _resolve_tier([g_leaves[i] for i in idxs],
                                       axes_t, self.op) if axes_t else None
            if group_tier is not None:
                schedule = "two_level"
            buckets = _plan_sync_buckets([g_leaves[i] for i in idxs],
                                         axes_t, world) \
                if axes_t else [list(range(len(idxs)))]
            prev = None
            for bucket in buckets:
                sel = [idxs[j] for j in bucket]
                leaves = [g_leaves[i] for i in sel]
                res = [res_leaves[i] for i in sel] if ef else None
                if prev is not None:
                    if res is not None:
                        (leaves, res), _ = lax.optimization_barrier(
                            ((leaves, res), prev))
                    else:
                        leaves, _ = lax.optimization_barrier(
                            (leaves, prev))
                k = bucket_no
                bucket_no += 1
                n_buckets += 1
                if axes_t:
                    synced, bres, tokens, wb, db = _wire_bucket_reduce(
                        leaves, res, axes_t, self.op, world,
                        group_codec, tier=group_tier,
                        scope=f"hvd_bucket{k}")
                    prev = tokens
                    wire_total += wb
                    dcn_total += db
                    # wire accounting covers SYNCED leaves only — local
                    # (axes-less) params never touch the interconnect
                    logical += sum(_leaf_nbytes(g) for g in leaves)
                else:                        # local params: no collective
                    synced = leaves
                    bres = [jnp.zeros_like(jnp.asarray(r)) for r in res] \
                        if ef else None
                # The apply fuses with THIS bucket's decode: one
                # elementwise pass per bucket instead of a second
                # whole-model pass after the full sync.
                with jax.named_scope(f"hvd_bucket{k}_apply"):
                    for j, i in enumerate(sel):
                        slots_i = tuple(slot_leaves[s][i]
                                        for s in range(opt.n_slots))
                        p_new, s_new = opt.apply_leaf(
                            ctx, p_leaves[i], synced[j], slots_i)
                        new_p[i] = p_new
                        for s in range(opt.n_slots):
                            new_slots[s][i] = s_new[s]
                        if ef:
                            new_res[i] = jnp.expand_dims(bres[j], 0)
        _record_wire_trace(
            codec.tier if codec is not None else "none",
            logical,
            wire_total if (codec is not None or schedule != "flat")
            else logical,
            n_buckets, ef, schedule=schedule, dcn_wire=dcn_total)
        params_out = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params), new_p)
        slots_out = tuple(
            jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(state.opt.slots[s]),
                new_slots[s])
            for s in range(opt.n_slots))
        residual_out: Any = ()
        if ef:
            residual_out = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(state.residual), new_res)
        return params_out, DistributedApplyState(
            EpilogueOptState(scalars, slots_out), residual_out)


def distributed_apply(optimizer: EpilogueOptimizer, *,
                      op: ReduceOp = ReduceOp.AVERAGE,
                      axis: Optional[Union[str, tuple]] = None,
                      sync_axes: Any = None,
                      compression: type = Compression.none,
                      error_feedback: Optional[bool] = None,
                      mesh: Any = None) -> DistributedApply:
    """Build the fused sync+apply (optimizer-in-epilogue) counterpart of
    :func:`DistributedOptimizer`: gradients are bucketed, wire-compressed,
    reduced, and the optimizer update is applied per bucket inside the
    decompress epilogue — no separate whole-model optimizer pass. See
    :class:`DistributedApply`; trainer integration:
    ``parallel.trainer.make_transformer_train_step_fused``."""
    return DistributedApply(optimizer, op=op, axis=axis,
                            sync_axes=sync_axes, compression=compression,
                            error_feedback=error_feedback, mesh=mesh)


def DistributedAdasumOptimizer(
    optimizer: optax.GradientTransformation,
    axis: Union[str, tuple],
    compression: type = Compression.none,
) -> optax.GradientTransformation:
    """Adasum *delta* optimizer (ref torch/optimizer.py:345
    ``_DistributedAdasumOptimizer`` and its delta-trick rationale at
    :414-427): each worker computes its inner optimizer's parameter delta
    from LOCAL gradients, and the deltas — not the gradients — are
    adasum-combined across workers. This keeps adaptive-optimizer
    statistics (momentum, Adam moments) consistent with the local
    gradient scale, which is what makes Adasum's scale-invariant
    combination sound for adaptive methods.

    Requires an explicit mesh ``axis`` (adasum is a real collective; the
    auto/XLA-inserted path cannot express it). Use inside shard_map/pmap,
    like the explicit-axis mode of :func:`DistributedOptimizer`.
    """
    if axis is None:
        raise ValueError(
            "DistributedAdasumOptimizer needs an explicit mesh axis — the "
            "delta combination is an adasum collective, which auto mode "
            "(XLA-inserted allreduce) cannot express")
    axes = axis if isinstance(axis, tuple) else (axis,)

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(updates, state, params=None):
        # Local delta from local gradients...
        deltas, new_state = optimizer.update(updates, state, params)
        # ...then scale-invariant pairwise combination of the deltas.
        deltas = jax.tree.map(
            lambda d: _sync_leaf(d, axes, ReduceOp.ADASUM, compression),
            deltas)
        return deltas, new_state

    return optax.GradientTransformation(init_fn, update_fn)


def distributed_value_and_grad(
    loss_fn: Callable[..., jax.Array],
    op: ReduceOp = ReduceOp.AVERAGE,
    axis: Optional[Union[str, tuple]] = None,
    compression: type = Compression.none,
    sync_axes: Any = None,
    has_aux: bool = False,
) -> Callable:
    """``DistributedGradientTape`` analogue (ref tensorflow/__init__.py:1051):
    value_and_grad whose gradients are synced across the axis. When ``axis``
    is given the loss value is pmean'ed over it too (replicated); with only
    ``sync_axes`` the loss stays per-shard (the caller knows its own data
    axes — average there)."""
    vg = jax.value_and_grad(loss_fn, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        val, grads = vg(*args, **kwargs)
        if axis is not None or sync_axes is not None:
            if sync_axes is not None:
                grads = jax.tree_util.tree_map(
                    lambda a, g: _sync_leaf(
                        g, [x for x in (a if isinstance(a, tuple) else (a,))
                            if x], op, compression),
                    sync_axes, grads,
                    is_leaf=lambda x: isinstance(x, tuple))
            else:
                axes = axis if isinstance(axis, tuple) else (axis,)
                grads = jax.tree.map(
                    lambda g: _sync_leaf(g, axes, op, compression), grads)
            loss_val = val[0] if has_aux else val
            loss_val = lax.pmean(loss_val, axis) if axis is not None \
                else loss_val
            val = (loss_val, val[1]) if has_aux else loss_val
        return val, grads

    return wrapped
