"""Serving subsystem (ROADMAP item 1, docs/serving.md): AOT
continuous-batching inference for the models under ``horovod_tpu/models``
that describe themselves to it (``cfg.serve_model()``: the flagship
TransformerLM, the shortcut-MoE latent-attention model, the hybrid
state-space / attention model).

- :mod:`~horovod_tpu.serving.kv_cache` — paged KV cache: fixed page
  pool, refcounted allocator, block tables, the shared-prefix
  hash-chain index (copy-on-write divergence), how a step body addresses
  a block of the flat pool, page writes, paged-attention reference.
- :mod:`~horovod_tpu.serving.model` — ``ServeModel``, what the engine
  asks of a model (step bodies, cache rows, device state, per-slot
  recurrent state); the models import it, never the engine.
- :mod:`~horovod_tpu.serving.engine` — AOT prefill/decode engine over
  the page pool: slots, pages, programs; it holds no model.
  Artifact-store-served (``serve`` kind) so warm boots compile nothing;
  ``load_for_serving`` is the train->serve handoff; speculative
  verify/draft executables when HOROVOD_SERVE_DRAFT is on.
- :mod:`~horovod_tpu.serving.scheduler` — iteration-level continuous
  batching with the coordinator's cycle/deadline idiom; accept-prefix
  speculative decode; the host-side n-gram drafter.
- :mod:`~horovod_tpu.serving.fleet` / :mod:`~horovod_tpu.serving.router`
  — hvdfleet: N replicas behind one occupancy/prefix-affinity router
  on the elastic member registry, with a queue-depth autoscaler,
  drain-safe scale-down and deterministic re-admission after a
  replica death.
"""

from typing import Any, Dict, Optional

from horovod_tpu.serving.engine import (  # noqa: F401
    ServeEngine,
    active_engine,
    load_for_serving,
    prefill_buckets,
)
from horovod_tpu.serving.kv_cache import (  # noqa: F401
    BlockTables,
    PageAllocator,
    PagePool,
    PrefixIndex,
    copy_page,
    paged_attention_reference,
    paged_decode_attention,
)
from horovod_tpu.serving.model import ServeModel  # noqa: F401
from horovod_tpu.serving.scheduler import (  # noqa: F401
    NGramDrafter,
    Request,
    ServeScheduler,
    active_scheduler,
)
from horovod_tpu.serving.fleet import (  # noqa: F401
    EngineReplica,
    ReplicaState,
    ServingFleet,
    active_fleet,
    fleet_stats,
)
from horovod_tpu.serving.router import (  # noqa: F401
    FleetRouter,
    FleetUnavailable,
)


def serving_stats() -> Optional[Dict[str, Any]]:
    """Live serving summary — the ``serving`` block of ``/healthz`` and
    the ``serve`` record block of the goodput ledger. None when no
    engine was built in this process (probes stay cheap)."""
    engine = active_engine()
    if engine is None:
        return None
    out: Dict[str, Any] = {"engine": engine.stats()}
    sched = active_scheduler()
    if sched is not None:
        out["scheduler"] = sched.stats()
    return out


def reset_for_tests() -> None:
    from horovod_tpu.serving import engine as _engine
    from horovod_tpu.serving import fleet as _fleet
    from horovod_tpu.serving import scheduler as _scheduler
    _engine.reset_for_tests()
    _scheduler.reset_for_tests()
    _fleet.reset_for_tests()
