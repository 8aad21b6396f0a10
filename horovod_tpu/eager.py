"""Horovod-style eager collective API (sync + async-handle variants).

Reference parity: the per-framework op surface — ``hvd.allreduce`` /
``allgather`` / ``broadcast`` / ``alltoall`` / ``reducescatter`` (+ grouped and
async variants, ``synchronize``/``poll``/``join``/``barrier``) as in
horovod/torch/mpi_ops.py:65-1283 and horovod/tensorflow/mpi_ops.py.

TPU-native semantics — the **rank-stacked convention**: the reference runs one
Python process per accelerator, so each rank passes *its own* tensor and the
runtime negotiates. Under JAX's single-controller SPMD there is one program
driving all chips, so an eager collective takes the whole world's per-rank
values as one *rank-stacked* global array ``x`` with ``x.shape[0] == size()``
(or a list of per-rank arrays), sharded over the mesh so row r lives on chip r.
Collectives then lower to one jitted shard_map program whose in/out shardings
make XLA emit the real ICI collective; results that are identical on every rank
(allreduce/allgather/broadcast) come back as ordinary replicated arrays, while
per-rank-differing results (alltoall/reducescatter) come back rank-stacked.

There is no negotiation protocol here: program order *is* the agreed collective
order (the property the reference's coordinator exists to establish,
operations.cc:383-402). Async variants return immediately — XLA dispatch is
already asynchronous — and ``synchronize`` blocks on the device result, the
analogue of HandleManager (ref torch/handle_manager.h).

**Frontend bridge**: every public op also accepts another framework's
``__dlpack__``-capable tensors (torch, TF, cupy, ...) — ingested zero-copy
where the exporter allows — and returns results in the SAME framework with
the original dtype restored; async handles convert at ``wait()``. This is
the role of the reference's per-framework adapters (torch/adapter_v2.cc
TorchTensor/TorchOpContext, mpi_ops_v2.cc:73 DoAllreduce). See
``examples/torch_frontend.py``.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the variance check off: replication of
    outputs (e.g. all_gather+prod for PRODUCT, masked-psum broadcast) is
    guaranteed by construction here but not always provable by
    shard_map's static analysis."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.fusion import fuse_apply
from horovod_tpu.ops.reduce_ops import ReduceOp, check_supported
from horovod_tpu.runtime.context import get_context

_name_lock = threading.Lock()
_name_counter = 0

_wait_hist = None


def _m_wait_hist():
    """hvd_handle_wait_seconds, created on first use (module-import order:
    eager loads before the metrics wiring in some entry points)."""
    global _wait_hist
    if _wait_hist is None:
        from horovod_tpu import metrics as M
        _wait_hist = M.histogram(
            "hvd_handle_wait_seconds",
            "Wall time a synchronize()/wait() blocked on an async "
            "collective handle (dispatch + device completion)")
    return _wait_hist


def _auto_name(prefix: str) -> str:
    global _name_counter
    with _name_lock:
        _name_counter += 1
        return f"{prefix}.noname.{_name_counter}"


class Handle:
    """Async-collective handle (ref torch/handle_manager.h HandleManager: int
    handle -> Status future).

    Two lifecycles:
    - *immediate*: constructed with a value already dispatched to XLA
      (``Handle(name, value)``) — ``wait`` just blocks on the device result;
    - *pending*: created by the cycle coordinator (``Handle.pending(name)``)
      for an enqueued-but-not-yet-dispatched tensor; the coordinator resolves
      it (``_set_result``/``_set_error``) at the end of its fusion cycle, the
      analogue of the reference's completion callback
      (torch/mpi_ops_v2.cc:94 MarkDone).

    Outstanding handles are tracked by the stall inspector (ref
    stall_inspector.cc: ops submitted but never completing trigger warnings
    and, optionally, job shutdown)."""

    __slots__ = ("name", "_value", "_error", "_event", "_tracked",
                 "_coordinator", "_frontend")

    def __init__(self, name: str, value: Any):
        self.name = name
        self._value = value
        self._error: Optional[BaseException] = None
        self._event = threading.Event()
        self._event.set()
        from horovod_tpu.stall_inspector import get_stall_inspector
        get_stall_inspector().record_start(name)
        self._tracked = True
        self._coordinator = None
        self._frontend = None   # DLPack frontend tag (same-framework wait)

    def _flush_if_deferred(self) -> None:
        """Deterministic (multi-controller) coordinators defer dispatch to
        symmetric flush points; a synchronize/poll on a still-pending
        handle is one (program-order identical on every host)."""
        coord = self._coordinator
        if coord is not None and coord.deterministic \
                and not self._event.is_set():
            coord.run_cycle()

    @classmethod
    def pending(cls, name: str) -> "Handle":
        h = cls(name, None)
        h._event.clear()
        return h

    # -- coordinator-side resolution ----------------------------------------
    def _set_result(self, value: Any) -> None:
        self._value = value
        self._event.set()

    def _set_error(self, exc: BaseException) -> None:
        self._error = exc
        self._event.set()

    def _untrack(self) -> None:
        if self._tracked:
            from horovod_tpu.stall_inspector import get_stall_inspector
            get_stall_inspector().record_done(self.name)
            self._tracked = False

    def _retrack(self) -> None:
        """(Re)start the stall clock — deferred deterministic-mode entries
        track from dispatch, not enqueue (a parked request is not a
        stall)."""
        if not self._tracked:
            from horovod_tpu.stall_inspector import get_stall_inspector
            get_stall_inspector().record_start(self.name)
            self._tracked = True

    def result(self) -> Any:
        """The dispatched value (None while still queued in the coordinator).
        Foreign-frontend handles convert like wait() does — poll()/result()
        must not return a different framework than synchronize()."""
        if self._value is not None and self._frontend is not None:
            return _dlpack_export(self._value, *self._frontend)
        return self._value

    def done(self) -> bool:
        self._flush_if_deferred()
        if not self._event.is_set():
            return False
        if self._error is not None:
            self._untrack()
            return True
        try:
            leaves = jax.tree_util.tree_leaves(self._value)
            ready = all(
                leaf.is_ready() if hasattr(leaf, "is_ready") else True
                for leaf in leaves)
        except Exception:
            ready = True
        if ready:
            self._untrack()
        return ready

    def wait(self) -> Any:
        t_wait0 = time.perf_counter()
        from horovod_tpu.tracing import spans as _trace
        wait_span = _trace.span(
            self.name, cat=_trace.CAT_WAIT,
            attrs={"op": "handle.wait"} if _trace.enabled() else None)
        wait_span.__enter__()
        try:
            self._flush_if_deferred()
            if not self._event.is_set():
                from horovod_tpu.timeline import WAIT, get_timeline
                tl = get_timeline()
                if tl.active:
                    with tl.span(self.name, WAIT, mirror=False):
                        self._event.wait()
                else:
                    self._event.wait()
            if self._error is not None:
                raise self._error
            try:
                jax.block_until_ready(self._value)
            except Exception as exc:
                # Async completion (the default) resolves handles at dispatch
                # time, so a device/host failure surfaces HERE — in elastic
                # mode it must be the recoverable error type the
                # hvd.elastic.run retry loop catches (ref
                # WaitForEventsElastic gpu_operations.cc:98-106).
                from horovod_tpu.config import knobs
                if knobs.get("HOROVOD_ELASTIC"):
                    from horovod_tpu.elastic.exceptions import \
                        HorovodInternalError
                    raise HorovodInternalError(
                        f"collective {self.name} failed on device: "
                        f"{exc}") from exc
                raise
            if self._frontend is not None:
                return _dlpack_export(self._value, *self._frontend)
            return self._value
        finally:
            wait_span.__exit__(None, None, None)
            _m_wait_hist().observe(time.perf_counter() - t_wait0)
            self._untrack()

    def __del__(self):  # dropped handle: stop tracking, no stall false-alarm
        try:
            self._untrack()
        except Exception:
            pass


def synchronize(handle: Handle) -> Any:
    """Block until the handle's collective finished; return its result
    (ref torch/mpi_ops.py:1237 synchronize)."""
    return handle.wait()


def poll(handle: Handle) -> bool:
    """True if the async op completed (ref torch/mpi_ops.py poll)."""
    return handle.done()


# ---------------------------------------------------------------------------
# input normalization
# ---------------------------------------------------------------------------

def _ctx():
    return get_context()


def _pset_key(process_set) -> int:
    """Cache-key component for a process set. Ids are allocated monotonically
    and never reused (ProcessSetTable._next_id), so an id uniquely names a
    membership for the context's lifetime."""
    return 0 if process_set is None else process_set.process_set_id


def _rank_axes(ctx):
    return tuple(ctx.topology.flat_axes)


def _joined_for(ctx, process_set) -> tuple:
    """The join registry governing an op: the Context's for the global set,
    the set's own otherwise (ref process_set.h:26 per-set joined state)."""
    if process_set is None or process_set.process_set_id == 0:
        return tuple(ctx.joined_ranks)
    return tuple(process_set.joined_ranks)


def _op_axis(ctx):
    """Axis spec collectives should reduce over — every mesh axis, for the
    global set AND subgroups alike: subgroup process sets pass linearized
    flat ranks as multi-axis ``axis_index_groups``
    (ops/collectives._resolve_groups for reductions;
    ``_uniform_partition_groups`` for the shape-changing
    allgather/alltoall/reducescatter subgroup path), so they compose with
    hierarchical (cross, local) meshes the way the reference's per-set
    communicators stay independent of the hierarchy (process_set.h:26)."""
    axes = _rank_axes(ctx)
    return axes if len(axes) > 1 else axes[0]


def _stack_input(ctx, x) -> jax.Array:
    """Normalize to a rank-stacked device array sharded row-per-chip."""
    if isinstance(x, (list, tuple)):
        from horovod_tpu import native
        packed = native.pack_arrays(list(x))    # parallel host memcpy
        # np.stack, not jnp.stack: the stacked array must stay on HOST so
        # the multi-controller branch below still sees a non-jax.Array and
        # takes the collective-free placement path.
        x = packed if packed is not None else np.stack(
            [np.asarray(v) for v in x])
    n = ctx.size
    shape = np.shape(x)
    if not shape or shape[0] != n:
        raise ValueError(
            f"eager collectives take rank-stacked input with shape[0] == "
            f"size() == {n}; got shape {shape}. Stack per-rank values on "
            f"dim 0 (or pass a list of {n} arrays).")
    sharding = NamedSharding(ctx.topology.mesh, P(_rank_axes(ctx)))
    if jax.process_count() > 1 and not isinstance(x, jax.Array):
        # Multi-controller: jax.device_put of a HOST array onto a
        # cross-process sharding internally runs process_allgather +
        # assert_equal — a hidden cross-host collective per enqueue. That
        # taxes every eager op and, worse, deadlocks a divergent program at
        # the enqueue itself, before the coordinator's divergence checker
        # can diagnose it. Building the global array from this host's
        # addressable shards is collective-free (each host only ever reads
        # its own rows of the rank-stacked input).
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    return jax.device_put(jnp.asarray(x), sharding)


def _cached_jit(ctx, key, build):
    """Look up (or build) a jitted program in the context's shared
    executable cache. Keying fresh closures by their semantic signature is
    what makes the SYNC eager path O(1) in steady state — without it every
    call constructs a new ``jax.jit`` object and re-traces, the overhead the
    reference's ResponseCache exists to avoid (response_cache.h:45)."""
    from horovod_tpu.ops.coordinator import get_executable_cache
    return get_executable_cache(ctx).get_or_build(("sync",) + key, build)


def _arr_sig(x) -> tuple:
    return (tuple(x.shape), str(x.dtype))


def _run_sharded(ctx, per_shard_fn, x, out_replicated: bool,
                 name: str = "collective", cache_key=None):
    """Dispatch one sharded collective program. ``cache_key`` is the
    semantic signature of ``per_shard_fn`` (op kind + every scalar the
    closure captured); callers that pass it share compiled executables
    across calls via the context cache."""
    axes = _rank_axes(ctx)
    mesh = ctx.topology.mesh
    in_spec = P(axes)
    out_spec = P() if out_replicated else P(axes)

    def build():
        def wrapper(a):
            v = jnp.squeeze(a, 0)      # (1, *s) shard -> per-rank value
            out = per_shard_fn(v)
            return out if out_replicated else jnp.expand_dims(out, 0)

        return jax.jit(shard_map(wrapper, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec))

    if cache_key is None:
        fn = build()
    else:
        fn = _cached_jit(ctx, cache_key + _arr_sig(x), build)
    from horovod_tpu.timeline import DISPATCH, get_timeline
    tl = get_timeline()
    if tl.active:
        with tl.span(name, DISPATCH):
            return fn(x)
    return fn(x)


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# DLPack frontend bridge: accept another framework's tensors, return that
# framework's tensors (ref torch/adapter_v2.cc TorchTensor/TorchOpContext;
# DoAllreduce mpi_ops_v2.cc:73 — the reference's raison d'etre is ingesting
# torch/tf tensors; here any __dlpack__-capable array ingests zero-copy)
# ---------------------------------------------------------------------------

def _dlpack_tag(x):
    """Frontend module name ('torch', 'cupy', ...) if x is a FOREIGN
    __dlpack__-capable tensor, else None (numpy / jax / python scalars
    pass through untouched)."""
    if isinstance(x, (np.ndarray, jax.Array)) or np.isscalar(x):
        return None
    if not hasattr(x, "__dlpack__"):
        return None
    return type(x).__module__.split(".")[0]


def _dlpack_scan(x):
    """Tag of the first foreign tensor in x (x may be a list/tuple)."""
    if isinstance(x, (list, tuple)):
        for v in x:
            tag = _dlpack_tag(v)
            if tag:
                return tag
        return None
    return _dlpack_tag(x)


def _dlpack_import(x):
    """Zero-copy foreign tensor -> jax array (lists element-wise)."""
    def one(v):
        if _dlpack_tag(v) is None:
            return v
        is_torch = v.__class__.__module__.split(".")[0] == "torch"
        # torch refuses __dlpack__/numpy() on grad-requiring tensors —
        # ingest the detached view (the reference's adapters likewise
        # read the raw storage, torch/adapter_v2.cc).
        if is_torch and getattr(v, "requires_grad", False):
            v = v.detach()
        try:
            from jax import dlpack as jdl
            return jdl.from_dlpack(v)
        except Exception:
            pass
        # Host roundtrip fallback (dtype/layout/device the jax importer
        # rejects) — correctness over zero-copy. np.asarray raises
        # opaquely on device-resident torch tensors (CUDA/MPS), so torch
        # goes through an explicit detach+host copy first.
        if is_torch:
            v = v.detach().cpu()
            # bf16 has no numpy dtype on the frontend side:
            # reinterpret bits.
            if str(v.dtype) == "torch.bfloat16":
                import ml_dtypes
                return jnp.asarray(
                    np.asarray(v.view(__import__("torch").uint16))
                    .view(ml_dtypes.bfloat16))
            return np.asarray(v)
        try:
            return np.asarray(v)
        except Exception as e:
            dev = getattr(v, "device", "<unknown device>")
            raise TypeError(
                f"cannot ingest {type(v).__module__}.{type(v).__name__} "
                f"on {dev}: the zero-copy DLPack import was rejected and "
                f"the frontend offers no host conversion — copy the "
                f"tensor to CPU before passing it to horovod_tpu") from e
    if isinstance(x, (list, tuple)):
        return [one(v) for v in x]
    return one(x)


def _dlpack_export(value, tag: str, dtypes=None):
    """jax results -> the frontend's tensors, recursively over
    lists/tuples (alltoallv returns ``(rows_list, recv_splits)``).
    ``dtypes`` (a frontend dtype, or a positional list for grouped ops)
    restores the ORIGINAL input dtype — e.g. torch int64 reduced through
    jax's default x32 comes back int64, and bf16 survives the host-copy
    fallback. Restoration applies only within the same dtype family
    (float->float, int->int): auxiliary INTEGER outputs like alltoallv's
    recv_splits must not inherit a float input dtype."""
    def cast(t, d):
        if d is None:
            return t
        same_family = (t.is_floating_point()
                       == getattr(d, "is_floating_point", False)
                       and t.is_complex() == getattr(d, "is_complex",
                                                     False))
        return t.to(d) if same_family else t

    def one(a, d):
        if not isinstance(a, jax.Array):
            return a
        if tag == "torch":
            import torch
            try:
                # Zero-copy for single-device arrays; sharded/replicated
                # results cannot export dlpack and take the host copy.
                return cast(torch.from_dlpack(a), d)
            except Exception:
                arr = np.asarray(a)
                if arr.dtype.name == "bfloat16":   # ml_dtypes: torch
                    t = torch.from_numpy(           # rejects it directly
                        arr.view(np.uint16).copy()).view(torch.bfloat16)
                else:
                    t = torch.from_numpy(arr.copy())
                return cast(t, d)
        if tag == "tensorflow":
            import tensorflow as tf
            try:
                t = tf.experimental.dlpack.from_dlpack(a.__dlpack__())
            except Exception:
                t = tf.constant(np.asarray(a))
            if d is not None and hasattr(d, "is_floating") \
                    and d.is_floating == t.dtype.is_floating \
                    and d.is_complex == t.dtype.is_complex:
                t = tf.cast(t, d)
            return t
        try:
            import importlib
            mod = importlib.import_module(tag)
            return mod.from_dlpack(a)          # the array-API convention
        except Exception:
            return a                            # unknown frontend: jax out

    def walk(v, d):
        if isinstance(v, tuple):
            return tuple(walk(e, d) for e in v)
        if isinstance(v, list):
            if isinstance(d, list) and len(d) == len(v):
                return [walk(e, de) for e, de in zip(v, d)]
            return [walk(e, d) for e in v]
        return one(v, d if not isinstance(d, list) else
                   (d[0] if d else None))

    return walk(value, dtypes)


def _frontend_bridge(fn):
    """Wrap a public eager op so foreign (__dlpack__) input tensors ingest
    zero-copy and results come back in the SAME framework; async ops tag
    their Handle and convert at wait()."""
    import inspect
    first_param = next(iter(inspect.signature(fn).parameters))

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if args:
            x = args[0]
        elif first_param in kwargs:     # keyword call (e.g. xs=grads)
            x = kwargs[first_param]
        else:
            return fn(*args, **kwargs)
        tag = _dlpack_scan(x)
        if tag is None:
            return fn(*args, **kwargs)
        if isinstance(x, (list, tuple)):
            dtypes = [getattr(v, "dtype", None) if _dlpack_tag(v) else None
                      for v in x]
        else:
            dtypes = getattr(x, "dtype", None)
        converted = _dlpack_import(x)
        if args:
            args = (converted,) + args[1:]
        else:
            kwargs = dict(kwargs, **{first_param: converted})
        out = fn(*args, **kwargs)
        if isinstance(out, Handle):
            out._frontend = (tag, dtypes)
            return out
        return _dlpack_export(out, tag, dtypes)
    return wrapped


@_frontend_bridge
def allreduce(x, op: ReduceOp = ReduceOp.AVERAGE, process_set=None,
              prescale_factor: Optional[float] = None,
              postscale_factor: Optional[float] = None,
              name: Optional[str] = None) -> jax.Array:
    """Reduce rank-stacked values across chips; returns the (replicated)
    reduced tensor of shape x.shape[1:]. Default op AVERAGE matches the
    reference Python API (torch/mpi_ops.py allreduce)."""
    ctx = _ctx()
    op = check_supported(op)
    x = _stack_input(ctx, x)
    axis = _op_axis(ctx)
    # For a non-global set, non-members reduce only with themselves, so the
    # result differs per rank and comes back rank-stacked like alltoall.
    out_rep = process_set is None or process_set.process_set_id == 0
    joined = _joined_for(ctx, process_set)
    return _run_sharded(
        ctx,
        lambda v: C.allreduce(v, op=op, axis=axis, process_set=process_set,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor,
                              joined_ranks=joined),
        x, out_replicated=out_rep,
        name=name or _auto_name("allreduce"),
        cache_key=("allreduce", op, _pset_key(process_set), prescale_factor,
                   postscale_factor, joined))


def _enqueue_async(op_type: str, x, name: Optional[str], *, op=None,
                   process_set=None, prescale_factor=None,
                   postscale_factor=None, root_rank=0, splits=None,
                   group_id=None, group_size=0, stack: bool = True) -> Handle:
    """Create a pending handle and enqueue the request with the cycle
    coordinator (ref EnqueueTensorAllreduce operations.cc:1404 pushing into
    the background thread's TensorQueue). The coordinator's next cycle fuses
    compatible queued tensors and dispatches one program per bin."""
    from horovod_tpu.ops.coordinator import Entry, get_coordinator
    ctx = _ctx()
    if op is not None:
        op = check_supported(op)
    if stack:
        x = _stack_input(ctx, x)
    handle = Handle.pending(name or _auto_name(op_type))
    entry = Entry(name=handle.name, op_type=op_type, x=x, handle=handle,
                  op=op if op is not None else ReduceOp.AVERAGE,
                  process_set=process_set, prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor, root_rank=root_rank,
                  splits=splits, group_id=group_id, group_size=group_size)
    try:
        coordinator = get_coordinator(ctx)
        handle._coordinator = coordinator
        coordinator.enqueue(entry)
    except Exception:
        # The rejected handle must not untrack the ORIGINAL in-flight op of
        # the same name from the stall inspector when it is GC'd.
        handle._tracked = False
        raise
    return handle


@_frontend_bridge
def allreduce_async(x, op: ReduceOp = ReduceOp.AVERAGE, process_set=None,
                    prescale_factor=None, postscale_factor=None,
                    name: Optional[str] = None) -> Handle:
    return _enqueue_async("allreduce", x, name, op=op,
                          process_set=process_set,
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor)


@_frontend_bridge
def grouped_allreduce(xs: Sequence, op: ReduceOp = ReduceOp.AVERAGE,
                      process_set=None, prescale_factor=None,
                      postscale_factor=None,
                      name: Optional[str] = None) -> List[jax.Array]:
    """One fused collective for many tensors (ref grouped_allreduce
    torch/mpi_ops.py; fusion semantics fusion_buffer_manager.h)."""
    ctx = _ctx()
    op = check_supported(op)
    xs = [_stack_input(ctx, x) for x in xs]
    axis = _op_axis(ctx)
    mesh = ctx.topology.mesh
    axes = _rank_axes(ctx)

    joined = _joined_for(ctx, process_set)
    # Subgroup results differ per rank (non-members keep their own value),
    # so they come back rank-stacked like single allreduce does.
    out_rep = process_set is None or process_set.process_set_id == 0

    def build():
        def wrapper(*shards):
            vals = [jnp.squeeze(a, 0) for a in shards]
            red = lambda v: C.allreduce(v, op=op, axis=axis,
                                        process_set=process_set,
                                        prescale_factor=prescale_factor,
                                        postscale_factor=postscale_factor,
                                        joined_ranks=joined)
            outs = fuse_apply(red, vals)
            if out_rep:
                return tuple(outs)
            return tuple(jnp.expand_dims(o, 0) for o in outs)

        return jax.jit(shard_map(
            wrapper, mesh=mesh,
            in_specs=tuple(P(axes) for _ in xs),
            out_specs=tuple((P() if out_rep else P(axes)) for _ in xs)))

    fn = _cached_jit(
        ctx, ("grouped_allreduce", op, _pset_key(process_set),
              prescale_factor, postscale_factor, joined,
              tuple(_arr_sig(x) for x in xs)), build)
    return list(fn(*xs))


class _GroupedHandle(Handle):
    """Aggregates the per-tensor handles of one registered group; ``wait``
    returns the list of reduced tensors in input order."""

    __slots__ = ("_parts",)

    def __init__(self, name: str, parts: List[Handle]):
        super().__init__(name, None)
        self._parts = parts

    def done(self) -> bool:
        ready = all(h.done() for h in self._parts)
        if ready:
            self._untrack()
        return ready

    def wait(self) -> List[Any]:
        try:
            out = [h.wait() for h in self._parts]
            if self._frontend is not None:
                out = _dlpack_export(out, *self._frontend)
            return out
        finally:
            self._untrack()


_group_lock = threading.Lock()
_group_counter = 0


def _next_group_id() -> int:
    global _group_counter
    with _group_lock:
        _group_counter += 1
        return _group_counter


@_frontend_bridge
def grouped_allreduce_async(xs, op: ReduceOp = ReduceOp.AVERAGE,
                            process_set=None, prescale_factor=None,
                            postscale_factor=None,
                            name: Optional[str] = None) -> Handle:
    """Enqueue all tensors as one registered group: the coordinator fuses
    them atomically (ref GroupTable group_table.h; grouped entries never
    split across fusion buffers, controller.cc:330-377)."""
    gid = _next_group_id()
    base = name or _auto_name("grouped_allreduce")
    xs = list(xs)
    parts: List[Handle] = []
    try:
        for i, x in enumerate(xs):
            parts.append(_enqueue_async(
                "allreduce", x, f"{base}.{i}", op=op,
                process_set=process_set, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor, group_id=gid,
                group_size=len(xs)))
    except Exception as exc:
        # Abort the whole group: members already queued would otherwise be
        # deferred forever (the group can never complete) and their handles
        # would strand any waiter.
        from horovod_tpu.ops.coordinator import get_coordinator
        removed = get_coordinator(_ctx()).queue.remove_group(gid)
        abort = RuntimeError(f"grouped_allreduce {base} aborted: "
                             f"member {len(parts)} failed to enqueue: {exc}")
        for e in removed:
            e.handle._set_error(abort)
        for h in parts:
            if not h._event.is_set():
                h._set_error(abort)
        raise
    return _GroupedHandle(base, parts)


@_frontend_bridge
def allgather(x, process_set=None, name: Optional[str] = None,
              _joined: Optional[tuple] = None) -> jax.Array:
    """Concatenate per-rank tensors along dim 0. Accepts a rank-stacked array
    (uniform shapes) or a list of per-rank arrays with *different first dims*
    — the allgatherv path (ref MPIAllgather MPI_Allgatherv
    mpi_operations.cc:122): uneven inputs are padded to the max first dim,
    gathered in one collective, and re-sliced.

    ``_joined``: enqueue-time join-mask snapshot from the coordinator — a
    deferred dispatch must use the mask that was current when the op was
    issued, not the live registry (same contract as Entry.joined for
    allreduce)."""
    ctx = _ctx()
    if isinstance(x, (list, tuple)) and len({np.shape(v)[0] if np.ndim(v) else 0
                                             for v in x}) > 1:
        return _allgatherv(ctx, [jnp.asarray(v) for v in x], process_set)
    x = _stack_input(ctx, x)
    subgroup = process_set is not None and process_set.process_set_id != 0
    joined = set(_joined if _joined is not None
                 else _joined_for(ctx, process_set))
    if subgroup or joined:
        # Shape-changing subgroup collectives cannot be a single XLA group
        # collective (groups must be size-uniform), so they are expressed as
        # global-array ops — the SPMD partitioner inserts the communication.
        # Joined ranks likewise contribute NOTHING to a gather (ref JoinOp:
        # zero-extent contribution; per-set join state process_set.h:26),
        # so their rows are dropped.
        if subgroup:
            members = tuple(r for r in process_set.ranks
                            if r not in joined)
        else:
            members = tuple(r for r in range(ctx.size)
                            if r not in joined)

        # The gathered result is a GLOBAL array (same value for every rank),
        # so shard its rows over the mesh instead of replicating — a
        # replicated output would pin the full (members * rows) tensor on
        # every chip (O(world) memory per chip). Consumers that need it
        # whole re-gather lazily.
        out_rows = len(members) * int(x.shape[1])
        out_spec = P(_rank_axes(ctx)) if (
            out_rows and out_rows % ctx.size == 0) else P()

        def build():
            def f(arr):
                return jnp.concatenate([arr[m] for m in members], axis=0)

            return jax.jit(f, out_shardings=NamedSharding(
                ctx.topology.mesh, out_spec))

        return _cached_jit(
            ctx, ("gather_members", members) + _arr_sig(x), build)(x)
    axis = _op_axis(ctx)
    from horovod_tpu.config import knobs
    # The hierarchical-gather knob is consumed at TRACE time inside
    # C.allgather, so it must be part of the executable signature.
    hier = bool(knobs.get("HOROVOD_HIERARCHICAL_ALLGATHER"))
    return _run_sharded(ctx, lambda v: C.allgather(v, axis=axis),
                        x, out_replicated=True,
                        name=name or _auto_name("allgather"),
                        cache_key=("allgather", hier))


def _allgatherv(ctx, parts: List[jax.Array], process_set) -> jax.Array:
    """Uneven-first-dim gather via pad-to-max (the SPMD form: shards must
    be shape-uniform, so ragged rows pad to the largest contributor and
    re-slice after the gather).

    Bandwidth bound vs the reference's exact-size MPI_Allgatherv
    (mpi_operations.cc:122): the wire moves ``size * max_i(n_i)`` rows
    instead of ``sum_i(n_i)`` — an overhead factor of
    ``max(n_i) / mean(n_i)``, i.e. none for balanced inputs and up to
    ``size``x under worst-case skew (one big contributor, rest empty).
    Static shapes are what keep the op a single compiled XLA collective
    (exact sizes would need one program per size vector — a recompile per
    distinct skew pattern); workloads with persistent heavy skew should
    bucket contributions toward uniform sizes (the MoE capacity-factor
    approach, parallel/moe.py) rather than rely on ragged gathers."""
    sizes = [int(p.shape[0]) for p in parts]
    maxn = max(sizes)
    trailing = parts[0].shape[1:]
    for p in parts:
        if p.shape[1:] != trailing:
            raise ValueError("allgatherv requires matching trailing dims")
    padded = jnp.stack([
        jnp.concatenate([p, jnp.zeros((maxn - p.shape[0],) + trailing,
                                      p.dtype)]) if p.shape[0] < maxn else p
        for p in parts])
    gathered = allgather(padded, process_set=process_set)  # (size*maxn, ...)
    pieces = [gathered[r * maxn: r * maxn + sizes[r]]
              for r in range(len(parts))]
    return jnp.concatenate(pieces)


@_frontend_bridge
def allgather_async(x, process_set=None, name: Optional[str] = None) -> Handle:
    # Uneven-first-dim lists (allgatherv) keep the host-side pad/re-slice
    # path, so they enqueue unstacked and dispatch solo.
    uneven = isinstance(x, (list, tuple)) and len(
        {np.shape(v)[0] if np.ndim(v) else 0 for v in x}) > 1
    if uneven:
        return Handle(name or _auto_name("allgather"),
                      allgather(x, process_set=process_set))
    return _enqueue_async("allgather", x, name, process_set=process_set)


@_frontend_bridge
def broadcast(x, root_rank: int = 0, process_set=None,
              name: Optional[str] = None) -> jax.Array:
    """Every rank receives root's row (ref broadcast torch/mpi_ops.py;
    MPIBroadcast mpi_operations.cc:401)."""
    ctx = _ctx()
    x = _stack_input(ctx, x)
    axis = _op_axis(ctx)
    out_rep = process_set is None or process_set.process_set_id == 0
    return _run_sharded(
        ctx,
        lambda v: C.broadcast(v, root_rank=root_rank, axis=axis,
                              process_set=process_set),
        x, out_replicated=out_rep,
        name=name or _auto_name("broadcast"),
        cache_key=("broadcast", root_rank, _pset_key(process_set)))


@_frontend_bridge
def broadcast_async(x, root_rank: int = 0, process_set=None,
                    name: Optional[str] = None) -> Handle:
    return _enqueue_async("broadcast", x, name, root_rank=root_rank,
                          process_set=process_set)


@_frontend_bridge
def alltoall(x, splits=None, process_set=None,
             name: Optional[str] = None):
    """All-to-all: each rank's dim 0 is sliced into per-destination segments.

    - Even path (``splits is None``): rank-stacked x of shape (size, k*size, …)
      → rank-stacked result where out[r] = concat of segment r from every rank
      (one XLA AllToAll; ref NCCLAlltoall nccl_operations.cc:1156).
    - Uneven path (``splits``: (size, size) send matrix, splits[r][d] rows of
      x[r] go to rank d — the alltoallv of ref PrepareOutputAndParams
      collective_operations.h:199): segments are padded to the max split,
      exchanged in one collective, then re-packed. Returns (result_rows_list,
      received_splits) like the reference's (output, received_splits) pair.
    """
    ctx = _ctx()
    if splits is not None:
        return _alltoallv(ctx, x, np.asarray(splits, np.int64), process_set)
    x = _stack_input(ctx, x)
    if process_set is not None and process_set.process_set_id != 0:
        # Set-stacked result over member ranks (see allgather note on
        # subgroup shape-changing collectives).
        members = tuple(process_set.ranks)
        k = len(members)
        rows = int(x.shape[1])
        if rows % k != 0:
            raise ValueError(
                f"alltoall first dim {rows} not divisible by set size {k}")
        c = rows // k
        trailing = x.shape[2:]

        def build():
            def f(arr):
                segs = jnp.stack([arr[m] for m in members])  # (k, k*c, ...)
                segs = segs.reshape((k, k, c) + trailing)
                out = jnp.swapaxes(segs, 0, 1)               # (k, k, c, ...)
                return out.reshape((k, k * c) + trailing)

            return jax.jit(f, out_shardings=NamedSharding(
                ctx.topology.mesh, P()))

        return _cached_jit(
            ctx, ("alltoall_members", members) + _arr_sig(x), build)(x)
    axis = _op_axis(ctx)
    return _run_sharded(
        ctx, lambda v: C.alltoall(v, axis=axis),
        x, out_replicated=False,
        name=name or _auto_name("alltoall"),
        cache_key=("alltoall",))


def _alltoallv(ctx, x, splits: np.ndarray, process_set):
    """Uneven alltoall via the O(1)-trace index-matrix exchange.

    Bandwidth bound vs the reference's exact-size MPI_Alltoallv
    (mpi_operations.cc:441): chunks pad to the largest split, so the wire
    moves ``n^2 * max(splits)`` entries instead of ``sum(splits)`` — an
    overhead factor of ``n^2 * max / sum``: none for balanced splits, up
    to ``n^2``x in the degenerate worst case (a single nonzero split).
    The trade keeps ONE compiled collective across every split
    pattern (exact sizes would recompile per distinct matrix). Heavy
    persistent skew should bucket or cap splits (MoE capacity factor,
    parallel/moe.py) — same guidance as _allgatherv."""
    subgroup = process_set is not None and process_set.process_set_id != 0
    n = process_set.size() if subgroup else ctx.size
    # A rank-stacked ARRAY input stays whole (uniform row counts; O(1)
    # traced ops below); only a ragged LIST input pays per-part padding.
    arr = None
    if isinstance(x, (list, tuple)):
        parts = [jnp.asarray(v) for v in x]
        nparts = len(parts)
    else:
        arr = jnp.asarray(x)
        parts = None
        nparts = int(arr.shape[0])
    if subgroup:
        # Set-stacked semantics: accept either k member parts (with a (k, k)
        # splits matrix) or world-stacked parts with a (size, size) matrix
        # restricted to member rows/cols.
        members = list(process_set.ranks)
        if nparts == ctx.size and splits.shape == (ctx.size, ctx.size):
            if arr is not None:
                arr = arr[jnp.asarray(members)]
            else:
                parts = [parts[m] for m in members]
            splits = splits[np.ix_(members, members)]
            nparts = n
        elif nparts != n:
            raise ValueError(
                f"subgroup alltoallv takes {n} member parts (set-stacked) or "
                f"{ctx.size} world-stacked parts; got {nparts}")
    if splits.shape != (n, n):
        raise ValueError(f"splits must be ({n},{n}) send matrix, "
                         f"got {splits.shape}")
    if parts is not None:
        trailing = tuple(parts[0].shape[1:])
        dtype = parts[0].dtype
        row_counts = [int(p.shape[0]) for p in parts]
    else:
        trailing = tuple(arr.shape[2:])
        dtype = arr.dtype
        row_counts = [int(arr.shape[1])] * n
    for r in range(n):
        if int(splits[r].sum()) != row_counts[r]:
            raise ValueError(
                f"splits row {r} sums to {int(splits[r].sum())}, tensor has "
                f"{row_counts[r]} rows")
    cmax = int(splits.max()) if splits.size else 0
    recv_splits = splits.T  # received_splits[d][r] = rows d got from r
    if cmax == 0:
        return ([jnp.zeros((0,) + trailing, dtype) for _ in range(n)],
                jnp.asarray(recv_splits))
    # (size, size*cmax, ...) send buffer, segment [r, d] = rows of rank r
    # destined for rank d, zero-padded to cmax. Built by ONE device gather
    # from host-precomputed indices so the traced-op count is independent of
    # n — a per-segment Python loop would trace O(n^2) slice/pad ops and
    # blow up compile time at MoE rank counts (the reference keeps the same
    # O(n^2) split bookkeeping host-side, PrepareOutputAndParams
    # collective_operations.h:199-268).
    rmax = max(row_counts)
    if parts is None:
        stacked = jnp.concatenate(          # (n, rmax+1, ...); last row zero
            [arr, jnp.zeros((n, 1) + trailing, dtype)], axis=1)
    else:
        stacked = jnp.stack([
            jnp.concatenate(
                [p, jnp.zeros((rmax + 1 - p.shape[0],) + trailing, dtype)])
            for p in parts])                # (n, rmax+1, ...); last row zero
    pad_row = rmax                           # zero row on every rank
    offs = np.zeros((n, n), np.int64)
    offs[:, 1:] = np.cumsum(splits, axis=1)[:, :-1]
    jj = np.arange(cmax)
    idx = offs[:, :, None] + jj[None, None, :]          # (n, n, cmax)
    idx = np.where(jj[None, None, :] < splits[:, :, None], idx, pad_row)
    flat_idx = (np.arange(n)[:, None] * (rmax + 1)
                + idx.reshape(n, n * cmax)).reshape(-1)
    send = jnp.take(stacked.reshape((-1,) + trailing),
                    jnp.asarray(flat_idx), axis=0,
                    ).reshape((n, n * cmax) + trailing)
    if subgroup:
        # The padded exchange among members is a (k, k) segment transpose.
        recv = jnp.swapaxes(send.reshape((n, n, cmax) + trailing), 0, 1)
    else:
        recv = alltoall(send).reshape(  # (size, size*cmax, ...)
            (n, n, cmax) + trailing)
    # splits is host-side numpy, so the ragged output extraction uses static
    # indices (one gather per destination) — the data itself never
    # round-trips through the host.
    flat_recv = recv.reshape((n, n * cmax) + trailing)
    outputs = []
    for d in range(n):
        if not recv_splits[d].sum():
            outputs.append(jnp.zeros((0,) + trailing, dtype))
            continue
        oidx = np.concatenate([r * cmax + np.arange(int(recv_splits[d, r]))
                               for r in range(n)])
        outputs.append(jnp.take(flat_recv[d], jnp.asarray(oidx), axis=0))
    return outputs, jnp.asarray(recv_splits)


@_frontend_bridge
def alltoall_async(x, splits=None, process_set=None,
                   name: Optional[str] = None) -> Handle:
    return _enqueue_async("alltoall", x, name, splits=splits,
                          process_set=process_set, stack=False)


def _reduce_member_rows(ctx, x, members, op, prescale_factor,
                        postscale_factor):
    """Reduce the member rows of a rank-stacked array with ``op``; returns the
    replicated (rows, ...) result. Used by subgroup reducescatter paths."""

    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN,
                  ReduceOp.MAX, ReduceOp.PRODUCT):
        raise ValueError(f"reducescatter does not support {op}")

    def build():
        def f(arr):
            vals = jnp.stack([arr[m] for m in members])
            if prescale_factor is not None:
                vals = vals * jnp.asarray(prescale_factor, vals.dtype)
            if op == ReduceOp.SUM:
                acc = vals.sum(0)
            elif op == ReduceOp.AVERAGE:
                acc = vals.sum(0) / jnp.asarray(len(members), vals.dtype)
            elif op == ReduceOp.MIN:
                acc = vals.min(0)
            elif op == ReduceOp.MAX:
                acc = vals.max(0)
            else:
                acc = jnp.prod(vals, 0)
            if postscale_factor is not None:
                acc = acc * jnp.asarray(postscale_factor, acc.dtype)
            return acc

        return jax.jit(f, out_shardings=NamedSharding(
            ctx.topology.mesh, P()))

    return _cached_jit(
        ctx, ("reduce_member_rows", members, op, prescale_factor,
              postscale_factor) + _arr_sig(x), build)(x)


@_frontend_bridge
def reducescatter(x, op: ReduceOp = ReduceOp.AVERAGE, process_set=None,
                  prescale_factor=None, postscale_factor=None,
                  name: Optional[str] = None):
    """Reduce rank-stacked values, scatter dim-0 slices back (rank-stacked
    result of shape (size, rows/size, ...)). Uneven dim 0 follows the
    reference's split rule — earlier ranks get the extra rows
    (ref collective_operations.h:282-295) — returning a per-rank list."""
    ctx = _ctx()
    op = check_supported(op)
    x = _stack_input(ctx, x)
    subgroup = process_set is not None and process_set.process_set_id != 0
    n = process_set.size() if subgroup else ctx.size
    rows = int(x.shape[1])
    axis = _op_axis(ctx)
    if subgroup and rows % n == 0:
        # Set-stacked result (see allgather note on subgroup collectives).
        full = _reduce_member_rows(ctx, x, tuple(process_set.ranks), op,
                                   prescale_factor, postscale_factor)
        return full.reshape((n, rows // n) + x.shape[2:])
    if rows % n == 0 and not subgroup:
        return _run_sharded(
            ctx,
            lambda v: C.reducescatter(v, op=op, axis=axis,
                                      prescale_factor=prescale_factor,
                                      postscale_factor=postscale_factor),
            x, out_replicated=False,
            name=name or _auto_name("reducescatter"),
            cache_key=("reducescatter", op, prescale_factor,
                       postscale_factor))
    # Uneven: reduce fully, then slice *rows* per the reference's rule.
    if subgroup:
        full = _reduce_member_rows(ctx, x, tuple(process_set.ranks), op,
                                   prescale_factor, postscale_factor)
    else:
        full = allreduce(x, op=op, prescale_factor=prescale_factor,
                         postscale_factor=postscale_factor)
    base, rem = divmod(rows, n)
    outs, offset = [], 0
    for r in range(n):
        c = base + (1 if r < rem else 0)
        outs.append(full[offset:offset + c])
        offset += c
    return outs


@_frontend_bridge
def reducescatter_async(x, op: ReduceOp = ReduceOp.AVERAGE, process_set=None,
                        prescale_factor=None, postscale_factor=None,
                        name: Optional[str] = None) -> Handle:
    return _enqueue_async("reducescatter", x, name, op=op,
                          process_set=process_set,
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor, stack=False)


def barrier(process_set=None) -> None:
    """Block until every chip reached the barrier (ref BarrierOp
    collective_operations.h:340; torch/mpi_ops.py:1283). Under the single
    controller this dispatches a scalar psum and waits for it."""
    ctx = _ctx()
    x = jnp.zeros((ctx.size,), jnp.int32)
    out = allreduce(x, op=ReduceOp.SUM, process_set=process_set)
    jax.block_until_ready(out)


def join(rank: Optional[Union[int, Sequence[int]]] = None,
         process_set=None) -> int:
    """Reference Join (ref Request::JOIN message.h:65, JoinOp
    collective_operations.h:312, controller.cc:269-327,
    torch/mpi_ops.py:1261): a rank that exhausted its data joins; until all
    ranks joined, collectives take the op's identity from joined ranks and
    AVERAGE divides by the active count only, so uneven per-rank batch
    counts finish an epoch with correct averages.

    TPU-native form: the reference's join is a blocking per-process call —
    under single-controller SPMD the controller drives every rank's stream,
    so join is a REGISTRY: ``join(r)`` marks rank r (or several) joined and
    returns -1 while ranks remain; the call that completes the set (or a
    bare ``join()``, which joins every remaining rank) performs the barrier,
    RESETS the registry for the next epoch, and returns the last rank that
    joined — the reference's return contract.

    ``process_set`` scopes the join to a subgroup: its members join against
    that set's own registry, affecting only collectives issued on the set —
    the reference's per-set joined state (process_set.h:26); its user-facing
    ``join()`` is global-set only, so this is a superset.
    """
    ctx = _ctx()
    if process_set is None or process_set.process_set_id == 0:
        registry, members = ctx.joined_ranks, list(range(ctx.size))
    else:
        registry, members = process_set.joined_ranks, process_set.ranks
    if rank is not None:
        for r in (rank if isinstance(rank, (list, tuple)) else [rank]):
            r = int(r)
            if r not in members:
                raise ValueError(
                    f"join rank {r} is not a member of the process set")
            if r not in registry:
                registry.append(r)
        if len(registry) < len(members):
            return -1
    else:
        for r in members:
            if r not in registry:
                registry.append(r)
    last = registry[-1]
    registry.clear()
    barrier(process_set=process_set)
    return last
