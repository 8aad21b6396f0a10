"""Global framework context: init/shutdown and the rank/size query API.

Reference parity: ``hvd.init()`` / ``hvd.shutdown()`` / ``hvd.rank()`` etc.
(reference: horovod/common/basics.py:29 HorovodBasics; C API
operations.cc:928-1400). The reference spawns a C++ background communication
thread per process and rendezvouses via MPI or a Gloo HTTP KV store; the
TPU-native equivalent is much lighter: `jax.distributed.initialize` is the
rendezvous (when launched multi-host), the mesh is the communicator, and
collective ordering is inherited from the single-controller SPMD program order
instead of a negotiation protocol. The background *dispatch* loop used by the
eager/handle API lives in horovod_tpu/ops/coordinator.py.

Rank semantics on TPU: the unit of parallelism is the *chip* (the reference's is
the process, one per GPU). ``size()`` is the number of chips in the global
process set; ``rank()`` is this controller process's first chip's rank, and
``local_size()`` is chips owned by this process — a data-loading process feeds
shards [rank(), rank()+local_size()). Inside jit, per-chip rank is
``lax.axis_index`` (see ops/collectives.rank_in_jit).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import jax

from horovod_tpu.runtime.topology import Topology, build_topology

_lock = threading.RLock()
_context: Optional["Context"] = None


class NotInitializedError(RuntimeError):
    def __init__(self):
        super().__init__(
            "horovod_tpu has not been initialized; call hvd.init() first.")


class Context:
    """Process-wide framework state (reference: common/global_state.h:39)."""

    def __init__(self, topology: Topology):
        self.topology = topology
        self._shutdown = False
        # Registered process sets (id 0 = global). Filled by process_sets module.
        self.process_set_table = None
        # Eager-op coordinator (fusion cycle dispatcher). Lazily created.
        self.coordinator = None
        # Compiled-executable LRU shared by the coordinator's fused dispatch
        # AND the sync eager path (ops/coordinator.get_executable_cache) —
        # the single steady-state re-dispatch cache, like the reference's
        # per-process-set ResponseCache (response_cache.h:45). Lazy.
        self.executable_cache = None
        self.timeline = None
        # Join registry (ref controller.cc:269-327 joined state): ranks that
        # exhausted their data, in join order; subsequent collectives take
        # zero contributions from them until every rank joined.
        self.joined_ranks: list = []

    # -- queries (reference C API operations.cc:1107-1190) --
    @property
    def size(self) -> int:
        return self.topology.size

    @property
    def local_size(self) -> int:
        return len(jax.local_devices())

    @property
    def cross_size(self) -> int:
        return jax.process_count()

    @property
    def rank(self) -> int:
        # First chip owned by this process, in mesh-flat order.
        devs = self.topology.devices_flat()
        mine = [i for i, d in enumerate(devs)
                if d.process_index == jax.process_index()]
        return mine[0] if mine else 0

    @property
    def local_rank(self) -> int:
        return 0

    @property
    def cross_rank(self) -> int:
        return jax.process_index()


def init(
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Sequence[str]] = None,
    hierarchical: Optional[bool] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    dcn: Optional[int] = None,
) -> Context:
    """Initialize the framework (idempotent, like horovod_init
    operations.cc:852 InitializeHorovodOnce).

    When launched by the multi-host launcher, ``coordinator_address`` /
    ``num_processes`` / ``process_id`` trigger `jax.distributed.initialize`
    (the rendezvous analogue of the reference's Gloo HTTP KV store,
    gloo_context.cc:153-230).
    """
    global _context
    with _lock:
        if _context is not None and not _context._shutdown:
            return _context
        # Goodput accountant enters the 'init' phase (HOROVOD_GOODPUT):
        # rendezvous + topology + subsystem bring-up are init time.
        from horovod_tpu.goodput import accountant as _goodput
        _goodput.init_begin()
        # Environment wiring from the hvdrun launcher (runner/launch.py).
        if coordinator_address is None and os.environ.get(
                "HVD_TPU_COORDINATOR"):
            coordinator_address = os.environ["HVD_TPU_COORDINATOR"]
            num_processes = int(os.environ["HVD_TPU_NUM_PROCESSES"])
            process_id = int(os.environ["HVD_TPU_PROCESS_ID"])
        if coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        expect_np = os.environ.get("HVD_TPU_EXPECT_NP")
        if expect_np and devices is None and int(expect_np) != len(
                jax.devices()):
            raise RuntimeError(
                f"hvdrun requested -np {expect_np} chips but "
                f"{len(jax.devices())} are visible; use --virtual for a "
                f"virtual mesh or adjust -np")
        topology = build_topology(
            devices=devices,
            mesh_shape=mesh_shape,
            axis_names=axis_names,
            hierarchical=hierarchical,
            dcn=dcn,
        )
        _context = Context(topology)
        # Register the global process set (id 0).
        from horovod_tpu.parallel import process_sets as _ps
        _ps._attach(_context)
        # HOROVOD_TIMELINE=path starts tracing at init (ref op.cc:546-560).
        from horovod_tpu import timeline as _tl
        _tl.init_from_env()
        # HOROVOD_METRICS_* exports (HTTP server / JSON dump / cluster
        # aggregation) come up with the runtime.
        from horovod_tpu import metrics as _metrics
        _metrics.init_from_env()
        # Topology-derived gauges (hvd_world_size & co) come up with the
        # runtime; the resize commit point republishes them so they are
        # never stale across a live world change.
        _metrics.publish_topology_gauges()
        # HOROVOD_TRACE=1 turns the span recorder on with the runtime
        # (docs/tracing.md); the shutdown path exports the merged trace.
        from horovod_tpu.tracing import spans as _spans
        _spans.init_from_env()
        # Init complete: the goodput accountant leaves 'init' and its
        # gauges come up on the metrics plane started above.
        _goodput.init_end()
        return _context


def shutdown() -> None:
    """Tear down framework state (reference horovod_shutdown operations.cc:958)."""
    global _context
    with _lock:
        if _context is None:
            return
        if _context.coordinator is not None:
            _context.coordinator.shutdown()
        if _context.timeline is not None:
            _context.timeline.close()
        # Tracing export BEFORE the metrics plane goes down: followers
        # publish their span summaries, the leader writes the merged
        # Perfetto file into the trace dir (best-effort, never raises).
        from horovod_tpu.tracing import spans as _spans
        if _spans.enabled():
            from horovod_tpu.tracing import merge as _merge
            from horovod_tpu.utils.kvstore import distributed_kv
            _merge.export_on_shutdown(
                kv=distributed_kv(site="trace_merge"),
                process_index=jax.process_index(),
                process_count=jax.process_count())
            _spans.disable()
        # Run-ledger record BEFORE the metrics plane goes down (the
        # record folds the final goodput report + numerics summary);
        # no-op unless HOROVOD_GOODPUT_LEDGER is configured.
        from horovod_tpu.goodput import ledger as _ledger
        _ledger.write_on_shutdown()
        from horovod_tpu import metrics as _metrics
        _metrics.stop_exports()
        _context._shutdown = True
        _context = None


def is_initialized() -> bool:
    return _context is not None and not _context._shutdown


def get_context() -> Context:
    if _context is None or _context._shutdown:
        raise NotInitializedError()
    return _context


# -- module-level query functions (hvd.rank() style) --

def size() -> int:
    return get_context().size


def rank() -> int:
    return get_context().rank


def local_size() -> int:
    return get_context().local_size


def local_rank() -> int:
    return get_context().local_rank


def cross_size() -> int:
    return get_context().cross_size


def cross_rank() -> int:
    return get_context().cross_rank


def mesh():
    return get_context().topology.mesh


def is_homogeneous() -> bool:
    """True when every process owns the same number of chips
    (reference horovod_is_homogeneous operations.cc:1153)."""
    ctx = get_context()
    counts = {}
    for d in ctx.topology.devices_flat():
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1
