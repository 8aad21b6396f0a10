"""Typed runtime-knob registry.

The reference framework configures itself through ~30 ``HOROVOD_*`` environment
variables parsed at background-thread startup (reference: common/operations.cc:459-646,
full list common/common.h:115-149) that are mirrored 1:1 by ``horovodrun`` CLI flags
(runner/launch.py:356-544). We keep the same convention — every knob is an env var
with a CLI mirror — but centralize parsing in one typed registry instead of ad-hoc
``std::getenv`` calls, so the launcher, the runtime, and the autotuner share a single
source of truth and the autotuner can override knobs at runtime.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional


def _parse_bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


def _parse_size(v) -> int:
    """Byte size with optional kb/mb/gb (or k/m/g) suffix: '8MB' -> 8388608."""
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip().lower()
    for suffix, mult in (("gb", 1 << 30), ("mb", 1 << 20), ("kb", 1 << 10),
                         ("g", 1 << 30), ("m", 1 << 20), ("k", 1 << 10),
                         ("b", 1)):
        if s.endswith(suffix):
            return int(float(s[:-len(suffix)]) * mult)
    return int(float(s))


def _parse_bucket_bytes(v):
    """Gradient bucket size: plain byte size, or 'auto' — resolve from the
    AOT schedule-search cache (autotune.resolve_bucket_bytes) at trace
    time, falling back to the built-in default when no sweep has been run
    for this (model shape, topology)."""
    s = str(v).strip().lower()
    if s == "auto":
        return "auto"
    return _parse_size(v)


def _parse_ckpt_interval(v):
    """Checkpoint cadence: a step count, or 'auto' — CheckFreq-style
    dynamic tuning against the measured mean step time (see
    resilience/async_checkpoint). 0 disables interval-driven saves
    (explicit ``save()`` calls still work)."""
    s = str(v).strip().lower()
    if s == "auto":
        return "auto"
    return int(float(s))


def _parse_fusion_threshold(v):
    """Fusion threshold: plain byte size, or the per-axis form
    'local:64MB,cross:8MB' for hierarchical meshes where the fast local
    (ICI) axis and the slow cross (DCN) axis want different bin capacities
    (the reference autotunes its hierarchy/torus choice per backend,
    parameter_manager.h:42-67; per-axis thresholds are the fusion analogue).
    Returns an int (uniform) or a {'local': int, 'cross': int} dict."""
    s = str(v)
    if ":" not in s:
        return _parse_size(s)
    out = {}
    for part in s.split(","):
        kind, _, size = part.partition(":")
        kind = kind.strip().lower()
        if kind not in ("local", "cross"):
            raise ValueError(
                f"per-axis fusion threshold keys must be local/cross, "
                f"got {kind!r} in {s!r}")
        out[kind] = _parse_size(size)
    return out


@dataclasses.dataclass
class Knob:
    name: str                     # env var name, e.g. HOROVOD_FUSION_THRESHOLD
    default: Any
    type: Callable[[str], Any]
    help: str = ""
    tunable: bool = False         # may be overridden by the autotuner at runtime
    choices: Optional[tuple] = None


class KnobRegistry:
    """Registry of runtime knobs. Values resolve as: runtime override (autotuner or
    programmatic) > environment variable > default."""

    def __init__(self):
        self._knobs: Dict[str, Knob] = {}
        self._overrides: Dict[str, Any] = {}

    def register(self, name, default, type=str, help="", tunable=False, choices=None):
        if type is bool:
            type = _parse_bool
        self._knobs[name] = Knob(name, default, type, help, tunable, choices)
        return self._knobs[name]

    def get(self, name: str) -> Any:
        knob = self._knobs[name]
        if name in self._overrides:
            return self._overrides[name]
        raw = os.environ.get(name)
        if raw is None or raw == "":
            return knob.default
        val = knob.type(raw)
        if knob.choices is not None and val not in knob.choices:
            raise ValueError(
                f"{name}={val!r} not in allowed choices {knob.choices}")
        return val

    def set_override(self, name: str, value: Any) -> None:
        if name not in self._knobs:
            raise KeyError(f"unknown knob {name}")
        self._overrides[name] = value

    def clear_override(self, name: str) -> None:
        self._overrides.pop(name, None)

    def clear_all_overrides(self) -> None:
        self._overrides.clear()

    def knobs(self) -> Dict[str, Knob]:
        return dict(self._knobs)

    def snapshot(self) -> Dict[str, Any]:
        return {k: self.get(k) for k in self._knobs}


knobs = KnobRegistry()

# ---------------------------------------------------------------------------
# Core runtime knobs (names kept HOROVOD_* for drop-in env compatibility with
# the reference; reference parse sites cited per knob).
# ---------------------------------------------------------------------------

knobs.register("HOROVOD_FUSION_THRESHOLD", 128 * 1024 * 1024,
               _parse_fusion_threshold,
               help="Fusion buffer size in bytes; small tensors are packed into one "
                    "fused collective up to this size (ref operations.cc:515-520). "
                    "Accepts size suffixes ('64MB') and, on hierarchical meshes, "
                    "the per-axis form 'local:64MB,cross:8MB' (local = fast ICI "
                    "axis, cross = slow DCN axis).",
               tunable=True)
knobs.register("HOROVOD_GRADIENT_BUCKET_BYTES", 25 * 1024 * 1024,
               _parse_bucket_bytes,
               help="In-graph gradient sync (DistributedOptimizer explicit-axis "
                    "mode): split the gradient list into contiguous buckets of "
                    "at most this many bytes, ordered by reverse backward "
                    "position, and issue one all-reduce per bucket instead of "
                    "one for the whole model. Because each bucket's collective "
                    "data-depends only on its own gradients, XLA's latency-"
                    "hiding scheduler overlaps late-layer buckets' collectives "
                    "with the backward compute of earlier layers — the "
                    "reference's async per-parameter-hook overlap "
                    "(operations.cc:383-402, torch/optimizer.py:167-174) "
                    "expressed as compiler-visible dataflow. 0 = single fused "
                    "buffer (no overlap; the pre-round-5 behavior). 'auto' = "
                    "resolve from the AOT schedule-search cache (the "
                    "parameter-manager analogue for this knob: `bench.py "
                    "--overlap-report` with auto sweeps {8,16,25,50,100} MiB "
                    "through the real compiler, scores payload-weighted "
                    "hideable compute against collective count with the "
                    "SCALING.json ring-latency model, and caches the winner "
                    "per (gradient shapes, world size) — "
                    "autotune.resolve_bucket_bytes); a cache miss falls back "
                    "to 25 MiB with a warning, and in multi-controller runs "
                    "the leader's resolution is broadcast over the "
                    "jax.distributed KV store so host-local cache "
                    "differences cannot desync the traced program. Read at "
                    "TRACE time — set before the first compile (not "
                    "runtime-autotunable).")
knobs.register("HOROVOD_GRADIENT_COMPRESSION", "none", str,
               choices=("none", "bf16", "fp16", "fp8_e4m3", "fp8_e5m2"),
               help="Wire dtype of the fused gradient collectives "
                    "(compression.WireCodec): the packed bucket is cast "
                    "to this dtype before the all-reduce and decompressed "
                    "in the epilogue, so the reduction moves 2x (bf16/"
                    "fp16) or 4x (fp8) fewer bytes over ICI/DCN. fp8 "
                    "tiers carry a per-bucket global-amax scale (one "
                    "scalar pmax per bucket) sized so the cross-rank SUM "
                    "cannot overflow the wire dtype, and enable the "
                    "error-feedback residual by default (see "
                    "HOROVOD_GRADIENT_ERROR_FEEDBACK). Overrides the "
                    "tier implied by DistributedOptimizer(compression=); "
                    "'none' leaves the wire uncompressed unless a "
                    "compression= argument asks otherwise. Read at TRACE "
                    "time by the in-graph bucket path (set before the "
                    "first compile); the eager coordinator reads it per "
                    "dispatch and keys its executable cache on it, which "
                    "is what lets the online autotuner "
                    "(HOROVOD_AUTOTUNE_COMPRESSION) tune it mid-run. "
                    "When fp8 is safe: docs/compression.md.",
               tunable=True)
knobs.register("HOROVOD_GRADIENT_ERROR_FEEDBACK", "auto", str,
               help="Error-feedback residual for lossy wire compression "
                    "(compression stays convergent: the quantization "
                    "error of step t is added back into step t+1's "
                    "gradient before compression — Karimireddy et al. "
                    "2019). 'auto' (default) = on for the low-bit fp8 "
                    "tiers, off for bf16/fp16; '1' forces it on for any "
                    "lossy tier, '0' disables. The residual is PER-RANK "
                    "state carried in the optimizer state (leading "
                    "world-sized dim sharded over the sync axes), so it "
                    "rides the checkpointed TrainState and kill->resume "
                    "trajectories stay bitwise-identical. COST: one "
                    "f32 copy of the gradients in the optimizer state.")
knobs.register("HOROVOD_AUTOTUNE_COMPRESSION", False, bool,
               help="Online ParameterManager v2: include the wire-"
                    "compression tier (HOROVOD_GRADIENT_COMPRESSION) as "
                    "a tunable dimension of the Bayesian autotuner, "
                    "sampled over autotune.COMPRESSION_TIER_CANDIDATES "
                    "and republished to every host through the knob "
                    "registry / parameter synchronizer like the fusion "
                    "threshold. OPT-IN because the tier changes wire "
                    "NUMERICS, not just performance — enable it when a "
                    "lossy wire is acceptable for the run (the eager "
                    "path has no error-feedback state; see "
                    "docs/compression.md).")
knobs.register("HOROVOD_BUCKET_AUTO_CACHE", "", str,
               help="Path of the JSON cache for HOROVOD_GRADIENT_BUCKET_BYTES"
                    "=auto sweep winners, keyed by (gradient shapes, world "
                    "size). "
                    "Empty = ~/.cache/horovod_tpu/bucket_auto.json.")
knobs.register("HOROVOD_ARTIFACT_STORE", "", str,
               help="Directory of the persistent compiled-artifact store "
                    "(horovod_tpu/store/, docs/artifact_store.md): AOT-"
                    "compiled executables are serialized under a composite "
                    "fingerprint (jax/jaxlib + backend version, mesh "
                    "fingerprint, autotune.grad_signature, resolved program "
                    "knobs, HVD503 collective-order fingerprint) and served "
                    "across train / verify / resume / serve processes — a "
                    "preemption auto-resume or HOROVOD_VERIFY_STEP run "
                    "reaches step 1 compile-free on a warm store. Entries "
                    "publish with the crash-safe .tmp-then-rename protocol; "
                    "corrupt/truncated/version-skewed artifacts log and fall "
                    "back to recompile. Empty disables the store.")
knobs.register("HOROVOD_ARTIFACT_STORE_MAX_BYTES", 2 * 1024 * 1024 * 1024,
               _parse_size,
               help="Size budget of the compiled-artifact store: after each "
                    "publish, oldest-mtime entries are evicted (LRU — hits "
                    "re-touch mtime) until the store fits. Accepts kb/mb/gb "
                    "suffixes. 0 = unlimited.")
knobs.register("HOROVOD_CE_BLOCK_VOCAB", 1024, int,
               help="Vocab chunk width of the blockwise fused cross-entropy "
                    "(ops/blockwise_ce): the LM-head projection is streamed "
                    "in chunks of this many vocab columns through an online "
                    "logsumexp, and the backward recomputes per-chunk logits "
                    "— no [batch, seq, vocab] logits array ever materializes "
                    "in HBM (f32 logits at B=8/S=2048/V=32k would be 2.1 GB "
                    "x three round trips). Used by the single-chip and the "
                    "TP vocab-parallel CE alike (one shared core). 0 = "
                    "unfused reference path. Read at TRACE time.")
knobs.register("HOROVOD_FUSION_THRESHOLD_CROSS", 0, _parse_size,
               help="Fusion bin capacity override for collectives whose traffic "
                    "crosses the slow outer (DCN) mesh axis; 0 falls back to "
                    "HOROVOD_FUSION_THRESHOLD. A second autotune dimension on "
                    "hierarchical meshes (ref parameter_manager.h:42-67 tunes "
                    "hierarchy choice per backend).",
               tunable=True)
knobs.register("HOROVOD_CYCLE_TIME", 1.0, float,
               help="Coordinator cycle time in ms between fused dispatches "
                    "(ref operations.cc:533-537).", tunable=True)
knobs.register("HOROVOD_CACHE_CAPACITY", 1024, int,
               help="Response/executable cache capacity (ref global_state.h:89).")
knobs.register("HOROVOD_HIERARCHICAL_ALLREDUCE", False, bool,
               help="Two-level (local ICI x cross DCN) allreduce decomposition "
                    "(ref nccl_operations.h:231).", tunable=True)
knobs.register("HOROVOD_HIERARCHICAL_ALLGATHER", False, bool,
               help="Two-level allgather (ref mpi_operations.cc:224).", tunable=True)
knobs.register("HOROVOD_TORUS_ALLREDUCE", False, bool,
               help="2D torus allreduce: reduce-scatter over local axis, allreduce "
                    "over cross axis, allgather over local axis (fork-specific "
                    "NCCLTorusAllreduce, ref nccl_operations.cc:698-812).",
               tunable=True)
knobs.register("HOROVOD_DCN_MESH", "", str,
               help="Multi-slice (DCN) mesh shape: 'dcn,local' or "
                    "'dcn,cross,local' slice-major, e.g. '2,4' for 2 "
                    "slices of 4 chips or '4,2,4' for 4 slices of a 2x4 "
                    "in-slice torus. Produces a mesh whose OUTERMOST "
                    "axis is the slow cross-slice DCN tier "
                    "(runtime.topology.DCN_AXIS) — the two-level "
                    "collective tier (ops.collectives."
                    "two_level_allreduce, HOROVOD_DCN_SCHEDULE) keys off "
                    "its presence. Empty = infer slices from device "
                    "slice_index (TPU multi-slice) or "
                    "HOROVOD_DCN_VIRTUAL_SLICES. Wins over both.")
knobs.register("HOROVOD_DCN_VIRTUAL_SLICES", 0, int,
               help="Pretend the (flat-ordered) device list is split "
                    "into this many equal contiguous 'slices' and build "
                    "the DCN-tiered mesh accordingly — no multi-pod "
                    "hardware needed, so every two-level schedule, "
                    "manifest, and compression path is testable on the "
                    "8-device virtual CPU mesh (the tier-smoke CI step "
                    "and tests/test_dcn_tier.py run exactly this). 0/1 "
                    "disables; real device slice_index wins when "
                    "present unless HOROVOD_DCN_MESH overrides.")
knobs.register("HOROVOD_DCN_SCHEDULE", "auto", str,
               choices=("flat", "two_level", "auto"),
               help="Gradient-collective schedule on a DCN-tiered mesh: "
                    "'flat' = one allreduce over every axis (XLA "
                    "schedules the cross-slice hops), 'two_level' = "
                    "per-slice reduce-scatter -> cross-slice allreduce "
                    "of only the owned shard -> intra-slice all-gather "
                    "(the fork's NCCLTorusAllreduce blueprint, "
                    "nccl_operations.cc:698-812, with "
                    "HOROVOD_GRADIENT_COMPRESSION applied to the SLOW "
                    "cross-slice stage only — ICI traffic stays "
                    "full-width), 'auto' = score both with the "
                    "SCALING.json ICI-vs-DCN latency/bandwidth model "
                    "per payload (autotune.resolve_dcn_schedule). Read "
                    "at TRACE time by the in-graph bucket path; the "
                    "eager coordinator reads it per dispatch and keys "
                    "its executable cache on it, so ParameterManager v2 "
                    "can retune it mid-run as an ordinal dimension. "
                    "Ignored on meshes without a DCN axis. Tier "
                    "algorithm + when two-level wins: "
                    "docs/hierarchical.md.",
               tunable=True)
knobs.register("HOROVOD_TIMELINE", "", str,
               help="Path of Chrome-trace timeline file; 'DYNAMIC' enables runtime "
                    "start/stop (ref timeline.cc, operations.cc:1073-1105).")
knobs.register("HOROVOD_TIMELINE_MARK_CYCLES", False, bool,
               help="Mark coordinator cycles in the timeline.")
knobs.register("HOROVOD_AUTOTUNE", False, bool,
               help="Enable Bayesian autotuning of fusion threshold / cycle time "
                    "(ref parameter_manager.cc).")
knobs.register("HOROVOD_AUTOTUNE_LOG", "", str,
               help="CSV log of autotune samples (ref parameter_manager.cc:77-82).")
knobs.register("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3, int,
               help="Autotune warmup discard count (ref common.h:119-124).")
knobs.register("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10, int,
               help="Steps per autotune scoring sample.")
knobs.register("HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20, int,
               help="Max Bayesian-optimization samples before convergence.")
knobs.register("HOROVOD_STALL_CHECK_TIME_SECONDS", 60, int,
               help="Warn when some ranks submitted a tensor and others have not "
                    "for this long (ref stall_inspector.cc:26).")
knobs.register("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0, int,
               help="Abort the job after a stall persists this long; 0 disables "
                    "(ref stall_inspector.cc).")
knobs.register("HOROVOD_STALL_CHECK_DISABLE", False, bool,
               help="Disable the stall inspector.")
knobs.register("HOROVOD_DIVERGENCE_CHECK_EVERY", 1, int,
               help="Multi-controller mode: verify every K-th flush that all "
                    "hosts submitted the identical collective sequence "
                    "(digest exchange over the jax.distributed KV store); "
                    "0 disables the check (ref controller.cc:496 mismatch "
                    "validation). COST: each check is one KV set + one "
                    "blocking wait-for-slowest-host roundtrip on the "
                    "dispatch thread (measured ms/flush in PERF.md). This "
                    "is the BASE interval: after 3 consecutive clean "
                    "checks the effective interval doubles, up to "
                    "HOROVOD_DIVERGENCE_CHECK_MAX_INTERVAL; any unseen "
                    "request signature or coordinator requeue snaps back "
                    "(the reference's response-cache fast path, "
                    "response_cache.h:107). MUST be set identically on "
                    "every host (as must MAX_INTERVAL and "
                    "HOROVOD_CACHE_CAPACITY): the cadence state is folded "
                    "into each check's digest, so a per-host difference "
                    "surfaces as an immediate descriptive mismatch naming "
                    "the cadence line.")
knobs.register("HOROVOD_DIVERGENCE_CHECK_MAX_INTERVAL", 64, int,
               help="Ceiling for the steady-state divergence-check "
                    "interval (see HOROVOD_DIVERGENCE_CHECK_EVERY). Must "
                    "be uniform across hosts — the effective cadence is "
                    "part of the exchanged digest.")
knobs.register("HOROVOD_DIVERGENCE_TIMEOUT", 300, int,
               help="Seconds to wait for peers at a flush check before "
                    "raising DivergenceError (stall warnings name lagging "
                    "hosts after HOROVOD_STALL_CHECK_TIME_SECONDS).")
knobs.register("HOROVOD_LOG_LEVEL", "warning", str,
               help="trace|debug|info|warning|error|fatal (ref logging.h).")
knobs.register("HOROVOD_LOG_HIDE_TIMESTAMP", False, bool,
               help="Hide timestamps in log output.")
knobs.register("HOROVOD_DISABLE_GROUP_FUSION", False, bool,
               help="Keep registered groups from fusing with other tensors "
                    "(ref controller.cc:214-238).")
knobs.register("HOROVOD_ELASTIC", False, bool,
               help="Elastic mode: collectives raise recoverable errors instead of "
                    "hanging on failure (ref nccl_operations.h:55).")
knobs.register("HOROVOD_ELASTIC_GRACE_SECONDS", 30.0, float,
               help="Elastic launcher: how long surviving workers get to reach "
                    "their next commit and exit voluntarily after a topology "
                    "change before the launcher terminates them (the analogue "
                    "of the reference's HOROVOD_GLOO_TIMEOUT_SECONDS worker "
                    "drain window).")
knobs.register("HOROVOD_ELASTIC_RESIZE_MARGIN", 2, int,
               help="Live world resize (elastic/resize.py): steps between "
                    "the resize notice and the agreed quiesce step. The "
                    "first controller observing a host/slice loss (or a "
                    "grow notice) publishes stop_step = its current step + "
                    "this margin write-once to the jax.distributed KV "
                    "store; every controller quiesces at the published "
                    "step, so the pre-resize snapshot is consistent across "
                    "hosts. Must cover the cross-controller notice skew in "
                    "steps — non-proposing controllers poll the plan key "
                    "at the HOROVOD_PREEMPTION_POLL_SECONDS cadence, so "
                    "the margin must exceed poll_seconds/step_time (the "
                    "preemption HOROVOD_PREEMPTION_QUIESCE_MARGIN "
                    "analogue for resizes).")
knobs.register("HOROVOD_ELASTIC_RESIZE_TIMEOUT", 60.0, float,
               help="Live world resize: seconds a controller waits on the "
                    "KV resize-plan agreement (and the snapshot barrier "
                    "inside the quiesce) before abandoning the resize "
                    "attempt. An abandoned attempt leaves training on the "
                    "OLD world — resize is retried at the next notice; "
                    "partial resizes never happen (the plan commits "
                    "atomically after the snapshot).")
knobs.register("HOROVOD_FLASH_BLOCK_Q", 2048, int,
               help="Flash-attention Q block rows (Pallas kernel grid): what "
                    "one grid step holds in VMEM; the kernels cut it into "
                    "compute tiles of their own (flash_attention._FWD_TILE, "
                    "_BWD_TILE). Measured on v5e (PR 36; the three training "
                    "kernels alone at [4, 2048, 16, 64] bfloat16, causal, "
                    "ms a layer, Q x K blocks): 2048x1024 2.73, 1024x1024 "
                    "2.88, 512x1024 3.02, 2048x512 3.21, 1024x512 3.45, "
                    "512x512 3.55, 2048x2048 3.71 (3.73 before that PR, at "
                    "512x1024): a grid step costs 0.3-0.7 us before its "
                    "first pair, so blocks are large and the tiles inside "
                    "them follow the diagonal. Shrunk to the largest "
                    "lane-aligned divisor of the actual sequence length. "
                    "Read at TRACE time — set before the first compile "
                    "(not runtime-autotunable).")
knobs.register("HOROVOD_FLASH_BLOCK_K", 1024, int,
               help="Flash-attention K/V block rows (see "
                    "HOROVOD_FLASH_BLOCK_Q).")
knobs.register("HOROVOD_BATCH_D2D_MEMCOPIES", True, bool,
               help="Batch fusion-buffer pack/unpack into one fused kernel "
                    "(ref cuda_kernels.cu; here: one jitted scatter/gather). "
                    "Read where a collective is a launch of its own: the eager "
                    "coordinator's fused dispatch and DistributedOptimizer's "
                    "unbucketed sync. The jitted trainer step "
                    "(trainer.sync_gradients) packs nothing and does not read "
                    "it: one psum a leaf, combined by the compiler.")
knobs.register("HOROVOD_ENABLE_ASYNC_COMPLETION", True, bool,
               help="Do not host-sync after collectives; rely on XLA async dispatch "
                    "(ref gpu_operations.cc:93-115).")
knobs.register("HOROVOD_NUM_STREAMS", 1, int,
               help="Parallel dispatch lanes for independent fused collectives.")
knobs.register("HOROVOD_METRICS_PORT", 0, int,
               help="Port for the background HTTP metrics server serving "
                    "Prometheus text-format /metrics and a /healthz that "
                    "reflects stall/elastic state; 0 disables. Bound on "
                    "every process; in multi-controller runs process 0 "
                    "additionally serves cluster-wide sums aggregated from "
                    "follower snapshots over the jax.distributed KV store.")
knobs.register("HOROVOD_METRICS_DUMP", "", str,
               help="Path for periodic JSON metrics-snapshot dumps (written "
                    "atomically every HOROVOD_METRICS_DUMP_INTERVAL seconds "
                    "and once more at shutdown); empty disables.")
knobs.register("HOROVOD_METRICS_DUMP_INTERVAL", 30.0, float,
               help="Seconds between JSON snapshot dumps (see "
                    "HOROVOD_METRICS_DUMP).")
knobs.register("HOROVOD_METRICS_AGG_INTERVAL", 5.0, float,
               help="Multi-controller: seconds between follower metrics-"
                    "snapshot publications to the jax.distributed KV store "
                    "for leader-side /metrics aggregation.")

# Resilience knobs (resilience/: async off-step-path checkpointing,
# preemption-aware auto-resume, chaos testing — SURVEY L6).
knobs.register("HOROVOD_CKPT_DIR", "", str,
               help="Checkpoint directory for the resilience subsystem "
                    "(resilience.AsyncCheckpointer): crash-safe "
                    "manifest-committed snapshots with newest-k rotation. "
                    "Read by parallel.trainer.train_loop and the "
                    "auto-resume path; empty disables loop-managed "
                    "checkpointing.")
knobs.register("HOROVOD_CKPT_INTERVAL", "auto", _parse_ckpt_interval,
               help="Steps between async snapshots, or 'auto' — tune the "
                    "save frequency against the measured mean step time "
                    "(StepStats' hvd_step_duration_seconds) so the "
                    "on-step-path cost (the device->host copy; "
                    "serialization runs on a worker thread) stays under "
                    "HOROVOD_CKPT_OVERHEAD_BUDGET of total step time — "
                    "the CheckFreq dynamic-frequency policy (Mohan et "
                    "al., FAST'21). 0 disables interval-driven saves.")
knobs.register("HOROVOD_CKPT_OVERHEAD_BUDGET", 0.05, float,
               help="Target ceiling for checkpoint on-path overhead as a "
                    "fraction of training time when "
                    "HOROVOD_CKPT_INTERVAL=auto (0.05 = 5%).")
knobs.register("HOROVOD_CKPT_KEEP", 3, int,
               help="Newest-k checkpoint rotation depth for the resilience "
                    "checkpointer. Older committed snapshots are deleted "
                    "only AFTER the new manifest is durably committed "
                    "(crash-safe rotation).")
knobs.register("HOROVOD_CKPT_FORMAT", "auto", str,
               choices=("auto", "orbax", "pickle"),
               help="Serialization of resilience checkpoints: 'orbax' "
                    "(sharded, reshardable on restore via "
                    "restore_checkpoint(template=...)), 'pickle' "
                    "(per-process host-shard files; each host writes only "
                    "the shards it owns), 'auto' = orbax for "
                    "single-controller runs when orbax imports, else "
                    "pickle.")
knobs.register("HOROVOD_CKPT_COMMIT_TIMEOUT", 120.0, float,
               help="Multi-controller commit barrier: seconds the leader "
                    "waits for every host's shard (and followers wait for "
                    "the leader's commit record) over the jax.distributed "
                    "KV store before declaring the checkpoint failed "
                    "(the attempt is abandoned uncommitted; training "
                    "continues and restore-latest skips it).")
knobs.register("HOROVOD_PREEMPTION_FILE", "", str,
               help="Sentinel file watched by resilience.PreemptionHandler "
                    "(poll cadence HOROVOD_PREEMPTION_POLL_SECONDS): when "
                    "it appears — e.g. written by a node-agent relaying a "
                    "TPU maintenance event — training quiesces at an "
                    "agreed step, commits a final synchronous snapshot, "
                    "and exits with the resumable status (75). Files "
                    "older than process start are ignored (a stale notice "
                    "from a previous incarnation must not re-kill the "
                    "resumed run). Empty disables the watcher; SIGTERM/"
                    "SIGINT trigger the same path regardless.")
knobs.register("HOROVOD_PREEMPTION_POLL_SECONDS", 1.0, float,
               help="Poll interval of the preemption sentinel-file "
                    "watcher (see HOROVOD_PREEMPTION_FILE).")
knobs.register("HOROVOD_PREEMPTION_QUIESCE_MARGIN", 2, int,
               help="Steps of headroom the first preempted controller adds "
                    "when publishing the agreed stop step over the "
                    "jax.distributed KV store, so peers (at most one "
                    "collective-synchronized step apart) can all reach it "
                    "and snapshot the same step.")
knobs.register("HOROVOD_AUTO_RESUME", 0, int,
               help="Max automatic restarts by the launcher when a run "
                    "exits with the resumable status (75, preemption "
                    "snapshot committed) or dies to a signal: the command "
                    "is relaunched with HVD_RESUME_ATTEMPT incremented "
                    "and restores from the latest committed checkpoint in "
                    "HOROVOD_CKPT_DIR. 0 disables (mirror: hvdrun "
                    "--auto-resume).")
knobs.register("HOROVOD_CHAOS_SPEC", "", str,
               help="JSON fault-injection spec for resilience.chaos "
                    "(tests/drills ONLY): e.g. '{\"kill\": {\"1:17\": "
                    "9}, \"commit_deny\": [5], \"commit_delay\": "
                    "{\"7\": 0.5}, \"preempt_at\": 12, "
                    "\"only_generation\": 1}' — kill -9 rank 1 at step "
                    "17, deny the step-5 commit, delay the step-7 commit, "
                    "deliver a fake preemption notice at step 12, all "
                    "only in the first incarnation. The full-surface "
                    "matrix adds kv_unavailable (p/window/count KV "
                    "brownouts), kv_slow (injected KV latency), "
                    "net_partition (host-set-scoped KV blackout), "
                    "fs_transient (EIO on the checkpoint tmp/rename "
                    "path), data_worker_kill (data-service worker death "
                    "mid-epoch), clock_skew (per-host trace-anchor "
                    "shift), store_corrupt (artifact-store reads see "
                    "bit-rot; the store must recompile, never crash), "
                    "host_loss/slice_loss/host_return (live-resize "
                    "notices driving the ResizeCoordinator shrink/grow "
                    "drills, docs/elastic.md) — "
                    "grammar in docs/resilience.md. Empty "
                    "disables all injection.")

# Fault-domain runtime knobs (resilience/faults.py: retry policies,
# degraded-mode shedding, data-plane supervision — docs/resilience.md).
knobs.register("HOROVOD_FAULT_RETRY_DEADLINE", 30.0, float,
               help="Default TOTAL retry budget in seconds per "
                    "control-plane call site (backoff included). "
                    "Per-site overrides: HOROVOD_FAULT_POLICIES or "
                    "resilience.faults.register_policy.")
knobs.register("HOROVOD_FAULT_RETRIES", 5, int,
               help="Default attempt ceiling per control-plane call "
                    "before the retry budget is declared exhausted "
                    "(optional sites then shed; protocol-critical sites "
                    "fail loudly with a flight recording).")
knobs.register("HOROVOD_FAULT_RETRY_BASE", 0.1, float,
               help="Base backoff in seconds for the default retry "
                    "policy; attempt k waits base*2^k, capped at "
                    "HOROVOD_FAULT_RETRY_MAX_BACKOFF, minus a "
                    "deterministic jitter fraction (seeded by call site "
                    "+ attempt — hosts decorrelate, replays stay "
                    "bit-identical).")
knobs.register("HOROVOD_FAULT_RETRY_MAX_BACKOFF", 5.0, float,
               help="Backoff ceiling in seconds for the default retry "
                    "policy (see HOROVOD_FAULT_RETRY_BASE).")
knobs.register("HOROVOD_FAULT_RETRY_JITTER", 0.2, float,
               help="Deterministic jitter fraction [0,1) subtracted "
                    "from each backoff (see HOROVOD_FAULT_RETRY_BASE). "
                    "0 disables jitter.")
knobs.register("HOROVOD_FAULT_POLICIES", "", str,
               help="JSON per-site retry-policy overrides, e.g. "
                    "'{\"metrics\": {\"deadline_s\": 5, "
                    "\"max_attempts\": 2}, \"checkpoint_commit\": "
                    "{\"deadline_s\": 120}}'. Unknown fields in an "
                    "entry are warned about and the entry ignored; "
                    "sites not listed keep the HOROVOD_FAULT_RETRY_* "
                    "defaults. Site catalog: docs/resilience.md.")
knobs.register("HOROVOD_FAULT_PROBE_SECONDS", 5.0, float,
               help="Degraded mode: how often a shed optional site "
                    "(metrics publish, trace merge, straggler exchange, "
                    "autotune sync) gets one probe operation through — "
                    "the mechanism by which the end of a brownout is "
                    "observed and the fault domain heals back to "
                    "healthy.")
knobs.register("HOROVOD_FAULT_HEARTBEAT_SECONDS", 2.0, float,
               help="Data-service workers: cadence of the liveness "
                    "heartbeat each DataWorker sends to the "
                    "ComputeService registry.")
knobs.register("HOROVOD_FAULT_WORKER_DEADLINE", 10.0, float,
               help="Data-service supervision: a worker whose last "
                    "heartbeat is older than this is declared dead — "
                    "the registry stops listing it and consumers "
                    "deterministically reshard its pending work onto "
                    "survivors (resilience e2e: bitwise-identical "
                    "trajectory across the reshard).")

# Tracing knobs (horovod_tpu/tracing/: span recorder, device-profile
# attribution, flight recorder — docs/tracing.md).
knobs.register("HOROVOD_TRACE", False, bool,
               help="Enable the span-based distributed tracer at "
                    "hvd.init() (or where a ServeEngine is built): "
                    "trace.span(...) context managers across "
                    "the coordinator cycle, eager handle waits, "
                    "checkpoint/preemption/elastic/data paths and the "
                    "serve loop record into "
                    "a per-process ring buffer, exported as a Perfetto-"
                    "loadable Chrome trace at shutdown (multi-controller "
                    "runs merge every host's spans onto the leader's "
                    "timeline over the jax.distributed KV store). OFF "
                    "(the default) costs nothing on the step path: "
                    "span() returns a shared no-op context manager — no "
                    "allocation (benchmarked in tests/test_tracing.py) — "
                    "unless a JAX profiler session is active, which gets "
                    "each span as an hvd.<name> annotation.")
knobs.register("HOROVOD_TRACE_BUFFER_SPANS", 16384, int,
               help="Capacity of the tracing ring buffer, in spans. The "
                    "oldest spans fall off at capacity, so a week-long "
                    "run's recorder stays O(this) memory and a "
                    "stall/abort flight recording ships the LAST N spans "
                    "— the ones that explain the failure.")
knobs.register("HOROVOD_TRACE_DIR", "", str,
               help="Directory for trace artifacts: shutdown exports, "
                    "profile-capture windows, and the flight recordings "
                    "dumped by stall-inspector aborts and preemption "
                    "drains. Empty = '.hvdtrace' under the working "
                    "directory.")
knobs.register("HOROVOD_TRACE_PROFILE", "", str,
               help="Programmatic jax.profiler capture window: "
                    "'steps:N' (capture N steps starting at step 2, "
                    "skipping compile) or 'steps:N@S' (starting at step "
                    "S). The emitted trace-events JSON is parsed with a "
                    "stdlib-only reader, device ops are classified "
                    "collective vs compute, and the OBSERVED overlap "
                    "ratio / exposed-collective seconds / per-bucket "
                    "device durations are written to "
                    "profile_attribution.json in the trace dir and "
                    "exported as hvd_overlap_observed_ratio / "
                    "hvd_step_exposed_collective_seconds gauges "
                    "(tracing/profile.py; OVERLAP.json observed tier). "
                    "One window per process lifetime. Empty disables.")

# Goodput accounting + numerics-health telemetry + run ledger
# (horovod_tpu/goodput/: time-attribution accountant, streaming anomaly
# detectors, cross-run regression sentinel — docs/observability.md
# "Goodput & run health").
knobs.register("HOROVOD_GOODPUT", True, bool,
               help="Enable the goodput time-attribution accountant "
                    "(goodput/accountant.py): every second of run wall "
                    "time is attributed to exactly one phase (init, "
                    "compile, step-compute, exposed-collective, "
                    "input-wait, checkpoint, restart, degraded, idle), "
                    "published as the hvd_goodput_fraction / "
                    "hvd_goodput_phase_seconds gauges, the 'goodput' "
                    "block of /healthz and hvd.metrics_snapshot(), and "
                    "hvd.goodput_report(). COST: a few float ops under "
                    "one uncontended lock per phase transition (a "
                    "handful per step) — on by default.")
knobs.register("HOROVOD_GOODPUT_LEDGER", "", str,
               help="Path of the append-only per-run JSONL ledger "
                    "(goodput/ledger.py): one record per run at "
                    "hvd.shutdown() (and per bench.py measurement) with "
                    "the goodput phase breakdown, numerics summary, "
                    "bench metrics, knob fingerprint, and HVD503 "
                    "collective-order fingerprints. The history behind "
                    "`bench.py --regression-report`. Empty disables.")
knobs.register("HOROVOD_GOODPUT_REGRESSION_TOLERANCE", 0.05, float,
               help="Regression sentinel (`bench.py "
                    "--regression-report`): allowed fractional drop of "
                    "throughput vs the best prior BENCH round, and "
                    "absolute drop of goodput fraction vs the best "
                    "prior ledger record, before the verdict flips to "
                    "'regress' (0.05 = 5%).")
knobs.register("HOROVOD_NUMERICS", False, bool,
               help="Enable numerics-health telemetry "
                    "(goodput/numerics.py): cheap on-device aggregates "
                    "(per-bucket grad norms + nonfinite counts, loss, "
                    "update ratio) feed streaming anomaly detectors — "
                    "loss spike, grad-norm explosion, nonfinite "
                    "localized to its fusion bucket and parameter "
                    "names — that fire flight recordings and "
                    "hvd_numerics_anomalies_total instead of letting a "
                    "run silently rot. Read at TRACE time by the eager "
                    "coordinator's fused programs (keys the executable "
                    "signature).")
knobs.register("HOROVOD_NUMERICS_CHECK_EVERY", 10, int,
               help="Numerics monitor cadence: buffered device scalars "
                    "are converted and run through the detectors every "
                    "this many observations, so the forced device->host "
                    "sync happens at the cadence, not per step.")
knobs.register("HOROVOD_NUMERICS_ACTION", "warn", str,
               choices=("warn", "degrade", "abort"),
               help="Response when a numerics detector fires (a flight "
                    "recording + counter always ship): 'warn' logs "
                    "only; 'degrade' sheds the optional 'numerics' "
                    "fault-domain site so /healthz flips to degraded "
                    "until a clean check heals it; 'abort' raises "
                    "NumericsAnomalyError into the training loop.")
knobs.register("HOROVOD_NUMERICS_SPIKE_SIGMA", 6.0, float,
               help="Loss-spike detector threshold: anomaly when a loss "
                    "lands this many trailing standard deviations above "
                    "its EWMA mean (after warmup).")
knobs.register("HOROVOD_NUMERICS_GRADNORM_FACTOR", 10.0, float,
               help="Grad-norm explosion threshold: anomaly when the "
                    "global gradient norm exceeds this multiple of its "
                    "trailing EWMA (after warmup).")

# IR-tier step verification (analysis/ir.py hvd.verify_step; HVD5xx
# rule catalog in docs/analysis.md).
knobs.register("HOROVOD_VERIFY_STEP", "0", str,
               choices=("0", "1", "strict"),
               help="Run the IR-tier step verifier (hvd.verify_step: "
                    "unreduced gradients, implicit GSPMD resharding, "
                    "collective-order determinism, donation misses, "
                    "bf16 reduction drift — HVD5xx) once on the jitted "
                    "train step at trainer.train_loop startup, before "
                    "the first step executes. '1' logs findings as "
                    "warnings; 'strict' raises VerificationError on any "
                    "finding; '0' disables. COST: none beyond the "
                    "verification itself — the loop adopts the "
                    "verifier's AOT-compiled executable for dispatch "
                    "(analysis.ir.take_compiled), so the verification "
                    "compile IS the startup compile; the jit path only "
                    "recompiles if shapes/shardings change mid-run.")
knobs.register("HOROVOD_MODEL_BUDGET_SECONDS", 10.0, float,
               help="hvdmodel exploration budget: wall-clock seconds the "
                    "protocol model checker (hvdlint --model, HVD6xx) "
                    "spends enumerating schedules, split evenly across "
                    "the scenarios of one invocation. The DFS is "
                    "resumable in spirit — a bigger budget explores a "
                    "strict superset of schedules — so PR CI uses "
                    "seconds and the nightly -m slow tier minutes.")
knobs.register("HOROVOD_MODEL_MAX_CRASHES", 1, int,
               help="hvdmodel: ceiling on crash transitions injected "
                    "per explored schedule (each crash kills one "
                    "simulated process at a yield point, filesystem and "
                    "KV effects preserved). Scenarios declare their own "
                    "crash budget; the effective value is the smaller "
                    "of the two. 0 disables crash injection entirely.")
knobs.register("HOROVOD_MODEL_SEED", 0, int,
               help="hvdmodel exploration-order seed: nonzero shuffles "
                    "the order the DFS explores the alternative "
                    "transitions branched from each decision point, "
                    "diversifying the schedules a small budget reaches. "
                    "0 = deterministic default order. Counterexample "
                    "REPLAY ignores the seed — the recorded trace alone "
                    "determines the run (hvdmodel --replay).")
knobs.register("HOROVOD_VERIFY_RESHARD_MIN_BYTES", 1024 * 1024, _parse_size,
               help="HVD502 implicit-resharding threshold: all-gather/"
                    "collective-permute/all-to-all ops in the optimized "
                    "HLO smaller than this stay quiet (tiny resharding "
                    "of norm scales or counters is routine); bigger ones "
                    "must be covered by the expected-collectives "
                    "manifest (ops/fusion.expected_manifest). Accepts "
                    "size suffixes ('4MB').")
knobs.register("HOROVOD_VERIFY_DONATION_MIN_BYTES", 1024 * 1024, _parse_size,
               help="HVD504 donation-miss threshold: undonated or "
                    "unaliased state-like buffers below this many bytes "
                    "per argument are not reported. Accepts size "
                    "suffixes ('4MB').")

# Cost-model knobs (HVD7xx resource tier — analysis/cost.py walks the
# compiled HLO of a step and projects HBM traffic, tile-padding waste
# and peak per-device memory before anything runs; docs/analysis.md).
knobs.register("HOROVOD_COST_PAD_AMPLIFICATION", 1.5, float,
               help="HVD701 threshold: an instruction whose "
                    "(sublane x 128-lane) tile-padded HBM bytes exceed "
                    "its logical bytes by at least this factor is a "
                    "padding-amplification finding (the measured ResNet "
                    "C=64 -> 128-lane BN wall is exactly 2.0x, "
                    "PERF.md r2/r3).")
knobs.register("HOROVOD_COST_PAD_MIN_WASTE", 16 * 1024 * 1024, _parse_size,
               help="HVD701 floor: instructions wasting fewer padded "
                    "bytes than this per execution stay quiet (padding "
                    "on small scales/stats buffers is noise; the BN-wall "
                    "activations waste hundreds of MiB). Accepts size "
                    "suffixes ('16MB').")
knobs.register("HOROVOD_COST_HBM_GB", 16.0, float,
               help="HVD702 default per-device HBM budget in GiB (v5e "
                    "lite = 16); cost_report's hbm_budget_bytes argument "
                    "overrides per call. Projected peak (args + "
                    "transient liveness peak) above the budget is a "
                    "projected-OOM finding.")
knobs.register("HOROVOD_COST_RESTREAM_MIN_BYTES", 8 * 1024 * 1024,
               _parse_size,
               help="HVD703 floor: re-streamed intermediates smaller "
                    "than this (padded) stay quiet — multi-pass reads of "
                    "small buffers are cache-resident, not an HBM wall. "
                    "Accepts size suffixes ('8MB').")
knobs.register("HOROVOD_COST_RESTREAM_READS", 3, int,
               help="HVD703 threshold: minimum number of distinct "
                    "fusion-class consumers re-reading one HBM-resident "
                    "intermediate before it is flagged (the measured BN "
                    "chain reads activations 4-9x).")
knobs.register("HOROVOD_COST_REPLICATED_MIN_BYTES", 64 * 1024 * 1024,
               _parse_size,
               help="HVD704 floor: optimizer-state leaves replicated "
                    "across a data axis are only flagged above this "
                    "size (small momentum scalars are fine replicated; "
                    "multi-B-param Adam moments are not). Accepts size "
                    "suffixes ('64MB').")
knobs.register("HOROVOD_COST_ROOFLINE_TOL", 0.5, float,
               help="HVD705 tolerance: |projected/measured - 1| beyond "
                    "this fails the roofline-vs-measured comparison "
                    "(projected step time from the traffic/flop model at "
                    "SCALING.json cost_model_rates vs the committed "
                    "BENCH row).")

# Handoff-compatibility knobs (HVD8xx compat tier — analysis/compat.py
# certifies a committed training snapshot against a serving consumer
# from on-disk artifacts alone; docs/analysis.md#compat).
knobs.register("HOROVOD_COMPAT_DROPPABLE", "", str,
               help="HVD804: extra comma-separated regexes of snapshot "
                    "leaf paths that may drop silently at the "
                    "train->serve handoff, on top of the built-in set "
                    "(optimizer state, step counters, WireState "
                    "residuals — rules_compat.DROPPABLE_DEFAULT). "
                    "Any other leaf absent from the serving template is "
                    "a finding: a renamed param is a model served with "
                    "wrong weights.")
knobs.register("HOROVOD_COMPAT_STORE_KINDS", "serve", str,
               help="HVD803: comma-separated artifact-store entry kinds "
                    "that must have at least one warm (env-matching, "
                    "digest-intact) entry for the swap to be certified "
                    "recompile-free. Default covers the serving "
                    "engine's executables; add 'step' to also require a "
                    "warm train step.")
knobs.register("HOROVOD_COMPAT_ROLLBACK_DEPTH", 1, int,
               help="HVD805: how many previous committed generations "
                    "compat_report re-certifies against the same "
                    "consumer (rollback must be compatible in both "
                    "directions — a swap that cannot roll back cannot "
                    "be attempted). 0 disables the rollback check.")

# Serving knobs (horovod_tpu/serving/: AOT continuous-batching inference
# with a paged KV cache — ROADMAP item 1, docs/serving.md).
knobs.register("HOROVOD_SERVE_SLOTS", 8, int,
               help="Decode batch slots of the serving engine "
                    "(serving.ServeEngine): the batched decode step is "
                    "AOT-compiled at exactly this batch size and the "
                    "continuous-batching scheduler admits requests into "
                    "free slots at step boundaries (iteration-level "
                    "scheduling, Orca OSDI'22). More slots = higher "
                    "steady-state throughput, more HBM held by KV pages. "
                    "Read at engine build time (keys the compiled serve "
                    "executables and their artifact-store entries).")
knobs.register("HOROVOD_SERVE_PAGE", 128, int,
               help="Tokens per KV-cache page (serving.kv_cache.PagePool "
                    "— the PagedAttention granularity, vLLM SOSP'23). "
                    "128 matches the TPU lane width, which is what makes "
                    "a page one full score tile of the paged-decode "
                    "Pallas kernel; non-128-multiple pages stay correct "
                    "through the jnp fallback (supports() gates kernel "
                    "dispatch, as for the training flash kernel). Read "
                    "at engine build time.")
knobs.register("HOROVOD_SERVE_MAX_SEQ", 2048, int,
               help="Per-request context ceiling (prompt + generated "
                    "tokens) of the serving engine; sets the block-table "
                    "width (ceil(max_seq/page) page slots per request). "
                    "Requests whose prompt exceeds it are rejected with "
                    "a descriptive error. Read at engine build time.")
knobs.register("HOROVOD_SERVE_PAGES", 0, int,
               help="Total pages in the serving KV pool; 0 = "
                    "slots x ceil(max_seq/page) (every slot can hold a "
                    "full-length request — no oversubscription). Smaller "
                    "values oversubscribe HBM: admission blocks while "
                    "the free list cannot cover a request's worst case, "
                    "and eviction-on-finish returns its pages. Read at "
                    "engine build time.")
knobs.register("HOROVOD_SERVE_PREFILL_CHUNK", 256, int,
               help="Prefill chunk ceiling in tokens: prompts are "
                    "prefilled in chunks compiled at fixed power-of-two "
                    "bucket lengths up to this cap (one AOT executable "
                    "per bucket, served through the artifact store), so "
                    "a long prompt never stalls decode for more than "
                    "one chunk and no prompt length triggers a fresh "
                    "compile. Read at engine build time.")
knobs.register("HOROVOD_SERVE_QUEUE_DEADLINE", 0.001, float,
               help="Continuous-batching admission deadline in seconds "
                    "(the coordinator cycle-time idiom applied to "
                    "requests): when every decode slot is idle the "
                    "scheduler waits up to this long for traffic before "
                    "re-polling; while any slot is decoding, admission "
                    "happens at every step boundary regardless, so the "
                    "deadline never delays in-flight tokens.")
knobs.register("HOROVOD_SERVE_MAX_NEW_TOKENS", 128, int,
               help="Default generation cap per request when the "
                    "request itself does not set max_new_tokens; also "
                    "the per-request page-reservation worst case the "
                    "admission check holds the free list to.")
knobs.register("HOROVOD_SERVE_PREFIX_CACHE", False, bool,
               help="Shared-prefix KV page reuse (hvdspec, "
                    "docs/serving.md): admission matches a request's "
                    "prompt against a hash-chain index of resident "
                    "page-granularity token blocks, adopts the matched "
                    "pages refcounted into its block table, reserves "
                    "only the tail, and copy-on-writes the divergent "
                    "block. Off (default) every page has one holder "
                    "and retire frees immediately — the PR 15 "
                    "behavior. Read at engine build time.")
knobs.register("HOROVOD_SERVE_DRAFT", "off", str,
               help="Speculative-decode drafter: 'off' (plain decode), "
                    "'ngram[:N]' (host-side n-gram lookup over the "
                    "request's own history, order N, default 3 — no "
                    "extra device work), or 'truncate:N' (self-draft "
                    "from the target's first N layers, sharing the KV "
                    "page pool; verify overwrites the draft's page "
                    "writes with identical values). Any non-'off' "
                    "value builds the batched verify executable at "
                    "engine boot (artifact-store kind 'serve'). Read "
                    "at engine build time.")
knobs.register("HOROVOD_SERVE_SPEC_K", 4, int,
               help="Draft tokens proposed per slot per speculative "
                    "step; ONE verify executable scores all K+1 "
                    "positions per slot in a single decode-shaped step "
                    "(batch slots x (K+1)), committing 1..K+1 tokens "
                    "under the greedy accept-prefix rule. Keys the "
                    "verify executable's shape, so it is read at "
                    "engine build time; ignored while "
                    "HOROVOD_SERVE_DRAFT=off.")

# Fleet knobs (horovod_tpu/serving/fleet.py: multi-replica serving —
# router, occupancy autoscaler, drain-safe lifecycle; docs/serving.md
# "Fleet").
knobs.register("HOROVOD_FLEET_REPLICAS", 1, int,
               help="Initial serving replicas a ServingFleet boots "
                    "with (each its own ServeEngine + scheduler; all "
                    "share one artifact store, so every replica after "
                    "the first constructs warm with builds==0). "
                    "Clamped up to HOROVOD_FLEET_MIN_REPLICAS.")
knobs.register("HOROVOD_FLEET_MIN_REPLICAS", 1, int,
               help="Autoscaler floor: scale-down never drains below "
                    "this many READY replicas, and a replica kill with "
                    "no survivors grows back to at least one before "
                    "re-admitting the dead replica's requests.")
knobs.register("HOROVOD_FLEET_MAX_REPLICAS", 4, int,
               help="Autoscaler ceiling: scale-up stops here no matter "
                    "the queue depth (the HBM/host budget bound — each "
                    "replica holds a full KV page pool).")
knobs.register("HOROVOD_FLEET_SCALE_UP_DEPTH", 8, int,
               help="Queue-depth-per-ready-replica threshold of the "
                    "occupancy autoscaler (the hvd_serve_queue_depth "
                    "signal): when queued requests exceed this many "
                    "per READY replica, the fleet grows one replica in "
                    "the SAME scheduling cycle the pressure is "
                    "observed.")
knobs.register("HOROVOD_FLEET_SCALE_DOWN_IDLE", 64, int,
               help="Consecutive fully-idle fleet cycles (no queued, "
                    "prefilling, or decoding request anywhere) before "
                    "the autoscaler drains the newest replica. Drain "
                    "is admission-stop + run-to-completion — never a "
                    "drop.")
knobs.register("HOROVOD_FLEET_COOLDOWN", 16, int,
               help="Minimum fleet cycles between two autoscale "
                    "events (grow or drain) — the anti-flap guard; "
                    "chaos replica kills and operator drains are not "
                    "throttled by it.")
knobs.register("HOROVOD_FLEET_AFFINITY", True, bool,
               help="Prefix-affinity routing: a request whose prompt "
                    "prefix is resident in some replica's hash-chain "
                    "index routes there (PR 17's shared pages only hit "
                    "when common-prefix requests land on the SAME "
                    "replica). Off, placement is pure "
                    "join-shortest-queue.")

# TPU-native knobs (no reference analogue).
knobs.register("HOROVOD_TPU_NATIVE", True, bool,
               help="Use the native C++ runtime core (csrc/libhvdtpu_core.so: "
                    "fusion planner, timeline writer, segment pack) when "
                    "built; 0 forces the pure-Python fallbacks. Read at "
                    "first use by horovod_tpu.native.")
knobs.register("HOROVOD_TPU_PALLAS", "1", str,
               help="Pallas kernel dispatch for hot ops (flash attention): "
                    "'1' = on for TPU backends, '0' = always jnp fallback, "
                    "'interpret' = force the kernel in interpreter mode on "
                    "CPU (tests). Read by ops/pallas/flash_attention.")
knobs.register("HOROVOD_TPU_MESH_SHAPE", "", str,
               help="Comma-separated mesh shape, e.g. '4,2' for a 2D (local,cross) "
                    "mesh. Empty = 1D over all devices.")
knobs.register("HOROVOD_TPU_MESH_AXES", "", str,
               help="Comma-separated mesh axis names matching MESH_SHAPE.")
knobs.register("HOROVOD_TPU_DONATE_BUFFERS", True, bool,
               help="Donate input buffers to in-place collective executables.")
knobs.register("HOROVOD_TPU_MATMUL_PRECISION", "default", str,
               help="jax default_matmul_precision for framework-issued compute.")
