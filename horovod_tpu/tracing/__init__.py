"""hvdtrace — span-based distributed tracing + device-profile attribution.

The reference framework's Timeline (SURVEY §L5) traces every tensor's
NEGOTIATE/ALLREDUCE lifecycle on the host; this subsystem is the
TPU-native superset, in four pieces:

- ``spans``     — the recording core: ``trace.span("name", ...)`` context
                  managers into a per-process ring buffer (allocation-free
                  when ``HOROVOD_TRACE=0``), Perfetto/Chrome-trace export,
                  and the flight-recorder dump used by stall/abort paths.
- ``merge``     — cross-controller trace merge over the jax.distributed
                  KV store with per-host clock-offset estimation, so
                  multi-controller timelines land in ONE Perfetto file.
- ``profile``   — ``jax.profiler`` capture windows parsed by a
                  stdlib-only trace-events reader: *observed* comm/compute
                  overlap, exposed-collective time, and per-bucket
                  on-device durations (OVERLAP.json's ``observed`` tier).
- ``straggler`` — per-host step-time skew exchange: which HOST is slow,
                  exported as ``hvd_straggler_skew_seconds`` and named in
                  ``/healthz``.

Usage::

    from horovod_tpu import tracing as trace
    with trace.span("train.load_batch", cat=trace.CAT_DATA):
        batch = next(loader)

Spans must NEVER be opened inside jit/pjit/shard_map-traced bodies —
they would measure trace time, not run time (hvdlint HVD206); use
``jax.named_scope`` to label device ops instead.
"""

from horovod_tpu.tracing.spans import (  # noqa: F401
    CAT_CHECKPOINT,
    CAT_COORDINATOR,
    CAT_DATA,
    CAT_ELASTIC,
    CAT_HOST,
    CAT_PREEMPTION,
    CAT_SERVE,
    CAT_TIMELINE,
    CAT_TRAIN,
    CAT_WAIT,
    active,
    begin_async,
    disable,
    dump_flight_recording,
    enable,
    enabled,
    end_async,
    epoch_unix,
    gc_us,
    init_from_env,
    instant,
    record,
    record_interval,
    reset,
    snapshot,
    span,
    span_counts,
    summary,
    trace_dir,
    trace_id,
)
