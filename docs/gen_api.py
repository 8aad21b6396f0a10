"""Generate docs/api.md from the live public surface.

Run:  python docs/gen_api.py        (writes docs/api.md)

The api-doc test regenerates and diffs, so the page can never drift from
the code (same contract as the knobs table; ref docs/api.rst role).
"""

from __future__ import annotations

import enum
import inspect
import os
import sys

SECTIONS = [
    ("horovod_tpu", "Top-level API",
     "Initialization, topology queries, eager collectives, reduce ops, "
     "process sets, distributed optimizer, checkpointing."),
    ("horovod_tpu.ops.collectives", "In-jit collectives (`horovod_tpu.ops`)",
     "Traceable collective primitives over named mesh axes — call inside "
     "shard_map/pjit."),
    ("horovod_tpu.parallel.tensor_parallel", "Tensor parallelism",
     "Megatron-style column/row-parallel layers and vocab-parallel loss."),
    ("horovod_tpu.parallel.pipeline", "Pipeline parallelism",
     "GPipe microbatch rotation over a mesh axis."),
    ("horovod_tpu.parallel.sequence", "Sequence parallelism / ring attention",
     "Long-context attention sharded over the sequence axis."),
    ("horovod_tpu.parallel.moe", "Mixture-of-experts",
     "Expert-parallel MoE layer over an `ep` mesh axis."),
    ("horovod_tpu.elastic", "Elastic training",
     "State/commit/run wrappers, host discovery, recoverable errors."),
    ("horovod_tpu.resilience", "Resilience",
     "Async off-step-path checkpointing with crash-safe commit, "
     "preemption-aware quiesce/auto-resume, fault-injection harness."),
    ("horovod_tpu.store", "Compiled-artifact store (hvdstore)",
     "Disk-backed AOT executable cache across train / verify / resume "
     "/ serve: composite-fingerprint keys, crash-safe atomic publish, "
     "LRU size budget; see docs/artifact_store.md."),
    ("horovod_tpu.serving", "Serving (hvdserve)",
     "AOT continuous-batching inference: paged KV cache with free-list "
     "allocator and block tables, prefill/decode engine served "
     "compile-free from the artifact store, iteration-level scheduler, "
     "train->serve checkpoint handoff; see docs/serving.md."),
    ("horovod_tpu.models.granite_hybrid",
     "Hybrid state-space / attention model",
     "Mamba-2 layers with a grouped-query attention layer among every few, "
     "routed experts plus a shared expert in each; served through "
     "`ServeEngine` with a per-slot recurrent state beside the paged KV "
     "pool (`GraniteHybridConfig.serve_model()`); see docs/serving.md."),
    ("horovod_tpu.models.solar_open2",
     "Delta-rule hybrid model",
     "Gated delta-rule linear-attention (KDA) layers with a gated "
     "grouped-query attention layer among every few, sigmoid-routed "
     "experts plus a shared expert in each; served through `ServeEngine` "
     "on the hybrid stack's step with a matrix state a head and slot "
     "(`SolarOpen2Config.serve_model()`); see docs/serving.md."),
    ("horovod_tpu.models.olmo_hybrid",
     "Gated DeltaNet hybrid model",
     "Gated DeltaNet layers (a delta rule with one decay a head and state "
     "heads of 96 x 192) with one multi-head attention layer with q/k "
     "norms among every four, a dense SwiGLU after each, every sublayer "
     "normed after it; served through `ServeEngine` on the hybrid stack's "
     "step (`OlmoHybridConfig.serve_model()`); see docs/serving.md."),
    ("horovod_tpu.models.delta_rule",
     "The gated delta rule",
     "One implementation of the gated delta rule for the recurrent layers "
     "that run it (a decay a key channel or one a head, keys and values "
     "of their own widths): the one-step form, the chunked form, the "
     "layouts of a slot's state."),
    ("horovod_tpu.models.kimi_k2",
     "Latent-attention expert stack (Kimi K2)",
     "DeepSeek-V3's layer: multi-head latent attention under a "
     "YaRN-stretched rotary (`transformer.RopeScaling`), a leading dense "
     "SwiGLU layer, then sigmoid-routed experts plus a shared expert; "
     "served through `ServeEngine` on the latent cache and step of "
     "`models/mla.py` (`KimiK2Config.serve_model()`); see "
     "docs/serving.md."),
    ("horovod_tpu.callbacks", "Callbacks",
     "Keras-style training callbacks (broadcast, metric averaging, LR "
     "schedules, best-model checkpoint)."),
    ("horovod_tpu.integrations", "Cluster integrations",
     "Executor pool, Ray, Spark, estimator/model, artifact stores."),
    ("horovod_tpu.data", "Data loading",
     "Sharded array/Parquet loaders and the data-service client."),
    ("horovod_tpu.autotune", "Autotuning",
     "Bayesian knob tuning and cross-controller parameter sync."),
    ("horovod_tpu.timeline", "Timeline / profiling",
     "Chrome-trace timeline with XLA xplane mirroring."),
    ("horovod_tpu.tracing", "Distributed tracing (hvdtrace)",
     "Span recorder with allocation-free off path, cross-controller "
     "Perfetto merge, jax.profiler device attribution (observed "
     "comm/compute overlap, per-bucket device time), straggler "
     "detection, and the stall/abort flight recorder; see "
     "docs/tracing.md."),
    ("horovod_tpu.tracing.profile", "Device-profile attribution",
     "Stdlib-only trace-events reader, collective/compute classifier, "
     "interval algebra, and the HOROVOD_TRACE_PROFILE step-window "
     "capture driver."),
    ("horovod_tpu.tracing.straggler", "Straggler detection",
     "Per-host step-time skew over the jax.distributed KV store; "
     "hvd_straggler_skew_seconds + the named slowest host in "
     "/healthz."),
    ("horovod_tpu.metrics", "Metrics",
     "Unified counter/gauge/histogram registry with Prometheus /metrics "
     "and /healthz export, JSON snapshot dumps, and cluster aggregation."),
    ("horovod_tpu.checkpoint", "Checkpointing",
     "Orbax-backed sharded save/restore and rotation."),
    ("horovod_tpu.analysis", "Static analysis (hvdlint)",
     "SPMD-consistency / trace-safety / concurrency / knob-registry "
     "rule engine, IR-tier step verification (`hvd.verify_step`), and "
     "protocol model checking (`hvdmodel`, HVD6xx — exhaustive schedule "
     "exploration of the real coordination protocols with replayable "
     "counterexamples); CLI `python -m horovod_tpu.analysis`, rule "
     "catalog in docs/analysis.md."),
]


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    names = []
    for n, obj in vars(mod).items():
        if n.startswith("_") or inspect.ismodule(obj):
            continue
        defined_here = getattr(obj, "__module__", mod.__name__)
        # Top-level re-exports ARE the API; submodules list only their own.
        if mod.__name__ == "horovod_tpu" \
                or defined_here.startswith(mod.__name__):
            names.append(n)
    return sorted(names)


def _sig(obj) -> str:
    import re
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return ""
    # Default-value reprs carry memory addresses; strip for determinism.
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _doc1(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    first = doc.strip().splitlines()[0] if doc.strip() else ""
    return first


def generate() -> str:
    import importlib
    out = ["# API reference",
           "",
           "Generated from the live public surface by `docs/gen_api.py` "
           "— regenerate after changing exports (the docs test diffs "
           "this page against the code).",
           ""]
    for mod_name, title, blurb in SECTIONS:
        mod = importlib.import_module(mod_name)
        out += [f"## {title}", "", blurb, "",
                f"Module: `{mod_name}`", ""]
        for name in _public_names(mod):
            obj = getattr(mod, name)
            if inspect.isclass(obj):
                out.append(f"- **`{name}`** (class)"
                           + (f" — {_doc1(obj)}" if _doc1(obj) else ""))
                methods = [m for m, f in vars(obj).items()
                           if not m.startswith("_")
                           and (inspect.isfunction(f)
                                or isinstance(f, staticmethod))]
                for m in sorted(methods):
                    out.append(f"  - `.{m}{_sig(getattr(obj, m))}`")
            elif callable(obj):
                out.append(f"- `{name}{_sig(obj)}`"
                           + (f" — {_doc1(obj)}" if _doc1(obj) else ""))
            elif isinstance(obj, (str, int, float, bool, bytes, enum.Enum,
                                  type(None))):
                out.append(f"- `{name}` = `{obj!r}`")
            else:
                # Mutable singletons (e.g. global_process_set) repr their
                # live state, which depends on whether init() ran in this
                # process — render the type only so output is deterministic.
                out.append(f"- `{name}` (instance of "
                           f"`{type(obj).__name__}`)")
        out.append("")
    return "\n".join(out) + "\n"


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))
    text = generate()
    with open(os.path.join(here, "api.md"), "w") as f:
        f.write(text)
    print(f"wrote docs/api.md ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
