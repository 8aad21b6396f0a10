"""Driver benchmark: ResNet-50 synthetic training throughput on TPU.

Workload parity: examples/pytorch/pytorch_synthetic_benchmark.py in the
reference (ResNet-50, synthetic ImageNet batches, img/sec) — the harness
behind the published numbers in docs/benchmarks.rst (BASELINE.md). Baseline
for vs_baseline: the reference's 1656.82 img/s on 16 Pascal GPUs =
103.55 img/s per accelerator (docs/benchmarks.rst:32-43).

The step runs through the framework's own hot path — a
``hvd.DistributedOptimizer``-wrapped optax update inside a
``trainer.jit_step``-compiled program (honoring HOROVOD_TPU_DONATE_BUFFERS /
HOROVOD_TPU_MATMUL_PRECISION) — not a bare jax.jit, so any framework
overhead is inside the measurement.

Sweeps the per-chip batch size and reports the best configuration with MFU
(model FLOP utilization, FLOPs from XLA's compiled cost analysis against the
chip generation's peak bf16 FLOP/s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

``--scaling`` runs the scaling-efficiency harness for the BASELINE north
star (>=90 % efficiency at 256 chips) on hardware this environment does
not have: it (a) weak-scales the same framework step over 1/2/4/8-device
virtual CPU meshes (subprocesses — device count is fixed per process) and
(b) compiles the step for 8/64/256-device meshes WITHOUT executing,
extracting per-step collective op counts and byte volumes from the
optimized HLO. The per-device collective volume staying ~flat as the mesh
grows is the ring-collective property the 90 % target rests on; results
land in SCALING.json and one summary JSON line.
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC_PER_CHIP = 1656.82 / 16.0

# Peak dense bf16 FLOP/s per chip, keyed by the exact ``device_kind``
# string JAX reports (public spec sheets; a v5e chip reports
# "TPU v5 lite").
PEAK_BF16_FLOPS = {
    "TPU v2": 22.5e12, "TPU v3": 61.0e12 / 2,     # per chip: 2 cores
    "TPU v4": 275e12, "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12, "TPU v6e": 918e12,
    "TPU v6 lite": 918e12, "TPU7x": 2307e12,
}


def peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``; a kind that is not in the table is
    an error, not a default (an MFU against 0 is no measurement)."""
    kind = getattr(device, "device_kind", None)
    if kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"bench.py: no peak FLOP/s for device_kind {kind!r}; add it to "
            f"PEAK_BF16_FLOPS with its source (known: "
            f"{sorted(PEAK_BF16_FLOPS)})")
    return PEAK_BF16_FLOPS[kind]


def cpu_by_name() -> bool:
    """The caller asked for the CPU by name (JAX_PLATFORMS=cpu): CI and
    the tests, which check counts and control flow on the virtual mesh."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_chip(mode: str) -> None:
    """Measurement paths run on the TPU. Without one they fail instead of
    measuring whatever backend JAX fell back to — unless the caller asked
    for the CPU by name."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_by_name():
        raise SystemExit(
            f"bench.py {mode}: no TPU (JAX found {platform!r}). This is a "
            f"measurement path; set JAX_PLATFORMS=cpu to run its CPU "
            f"check by name.")


def build_step(model, optimizer, variables, mesh):
    """One full training-mode step (BN batch stats computed + running stats
    updated, like the reference harness' model.train()), compiled through
    the framework's jit_step so the donate/precision knobs apply."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.trainer import jit_step

    @jit_step
    def step(state, x, y):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            # batch-norm-free models (plain VGG) carry an empty
            # batch_stats collection through the same step shape.
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, upd.get("batch_stats", {})

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, new_stats, opt_state), loss

    repl = NamedSharding(mesh, P())
    params = jax.device_put(variables["params"], repl)
    batch_stats = jax.device_put(variables.get("batch_stats", {}), repl)
    opt_state = optimizer.init(params)
    return step, (params, batch_stats, opt_state)


def measure(step, state, x, y, n_warmup, n_steps):
    """(img/s over n_steps, final state). Timing closes with a host readback
    of the final loss — a device->host transfer is a completion barrier,
    and the steps serialize through the state dependence."""
    for _ in range(n_warmup):
        state, loss = step(state, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, loss = step(state, x, y)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"
    return x.shape[0] * n_steps / dt, state


def main() -> int:
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50, VGG16

    require_chip("train")
    peak = peak_flops(jax.devices()[0])
    hvd.init()
    mesh = hvd.mesh()
    n_chips = hvd.size()
    image_size = 224

    # --model vgg16: the reference headline table's bandwidth-worst-case
    # scaling workload (docs/benchmarks.rst:13-14 — 68 % @512 for VGG-16
    # vs 90 % for ResNet: ~138M params = ~5x the gradient payload).
    positional = [a for a in sys.argv[1:] if not a.startswith("-")]
    model_name = positional[0] if positional else "resnet50"
    if model_name not in ("resnet50", "resnet101", "vgg16", "inception3"):
        print(f"bench.py: unknown model {model_name!r} (choose resnet50, "
              f"resnet101, vgg16 or inception3)", file=sys.stderr)
        return 2
    if model_name == "vgg16":
        model = VGG16(num_classes=1000, dtype=jnp.bfloat16)
        batch_sweep = (32, 64, 128)
    elif model_name == "inception3":
        # Third workload of the headline scaling table (90% @512,
        # docs/benchmarks.rst:13-14; tf_cnn_benchmarks --model inception3).
        from horovod_tpu.models import InceptionV3
        model = InceptionV3(num_classes=1000, dtype=jnp.bfloat16)
        image_size = 299
        batch_sweep = (64, 128, 256)
    elif model_name == "resnet101":
        # The EXACT model behind the published 1656.82 img/s @16-GPU row
        # (tf_cnn_benchmarks resnet101, docs/benchmarks.rst:32-43) — the
        # apples-to-apples vs_baseline comparison.
        from horovod_tpu.models import ResNet101
        model = ResNet101(num_classes=1000, dtype=jnp.bfloat16,
                          folded_bn=True)
        batch_sweep = (64, 128, 256)
    else:
        # folded_bn: lane-folded batch norm (models/folded_bn.py) — measured
        # +1.9% on v5e (PERF.md round 3): BN stats/normalize for C=64
        # tensors read at full 128-lane occupancy through a free reshape.
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         folded_bn=True)
        batch_sweep = (64, 128, 256)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, image_size, image_size, 3),
                                     jnp.bfloat16))
    # Keep the init template on host: build_step re-places it per sweep
    # config, and donation (HOROVOD_TPU_DONATE_BUFFERS) would delete aliased
    # device buffers out from under the next build.
    variables = jax.tree.map(np.asarray, variables)
    optimizer = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), op=hvd.Average)

    from jax.sharding import NamedSharding, PartitionSpec as P
    data_sh = NamedSharding(mesh, P("hvd"))
    rng = np.random.RandomState(0)

    best = None   # (img/s, batch_per_chip, state, flops_per_step)
    for batch_per_chip in batch_sweep:
        batch = batch_per_chip * n_chips
        x = jax.device_put(
            jnp.asarray(rng.rand(batch, image_size, image_size, 3),
                        jnp.bfloat16), data_sh)
        y = jax.device_put(
            jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32), data_sh)
        try:
            step, state = build_step(model, optimizer, variables, mesh)
            flops = float(step.lower(state, x, y).compile()
                          .cost_analysis()["flops"])
            ips, state = measure(step, state, x, y, n_warmup=2, n_steps=10)
            if best is None or ips > best[0]:
                best = (ips, batch_per_chip, flops)
        except jax.errors.JaxRuntimeError as e:
            # Out of device memory at this batch: keep the best so far.
            # Anything else is a real failure and must surface.
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"bench.py: batch {batch_per_chip}/chip does not fit "
                  f"({str(e)[:80]!r})", file=sys.stderr)
            break
        finally:
            del x, y

    if best is None:
        print(f"bench.py: no sweep batch size fit in device memory for "
              f"{model_name} (all {batch_sweep} OOMed)", file=sys.stderr)
        return 1
    ips, batch_per_chip, flops_per_step = best
    # Final longer measurement at the winning batch size.
    batch = batch_per_chip * n_chips
    x = jax.device_put(
        jnp.asarray(rng.rand(batch, image_size, image_size, 3),
                    jnp.bfloat16), data_sh)
    y = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32), data_sh)
    step, state = build_step(model, optimizer, variables, mesh)
    # Best sustained window of three.
    from horovod_tpu import metrics as hvd_metrics
    run_base = hvd_metrics.runtime_totals()
    t_run0 = time.perf_counter()
    ips = 0.0
    for _ in range(3):
        w_ips, state = measure(step, state, x, y, n_warmup=1, n_steps=15)
        ips = max(ips, w_ips)
    run_wall = time.perf_counter() - t_run0
    run_coll = (hvd_metrics.runtime_totals()["collective_seconds"]
                - run_base["collective_seconds"])

    per_chip = ips / n_chips
    mfu = (ips / batch) * flops_per_step / n_chips / peak

    result = {
        "metric": f"{model_name}_synthetic_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        # The published per-GPU baseline is the ResNet-class number; other
        # models report absolute throughput only.
        # The published 1656.82/16 row IS resnet101 (tf_cnn_benchmarks);
        # resnet50 keeps the same baseline (the reference's pytorch
        # synthetic benchmark defaults to resnet50 at similar cost).
        "vs_baseline": (round(per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 3)
                        if model_name in ("resnet50", "resnet101")
                        else None),
        "batch_per_chip": batch_per_chip,
        "mfu": round(mfu, 4),
        "chip": jax.devices()[0].device_kind,
        # Runtime health from the unified metrics registry (cycle-time
        # percentiles, cache hit rate) + the measured windows' eager-layer
        # collective fraction — BENCH_*.json now carries health alongside
        # throughput. In-graph (DistributedOptimizer) collectives live
        # inside the XLA step, so a ~0 fraction here is expected.
        "runtime_metrics": dict(
            hvd_metrics.bench_summary(),
            collective_time_fraction=round(
                min(run_coll / run_wall, 1.0), 4) if run_wall > 0 else None),
    }
    print(json.dumps(result))
    # Run-ledger record (HOROVOD_GOODPUT_LEDGER): the bench metrics ride
    # along with the goodput breakdown + fingerprints, so the regression
    # sentinel can read one history instead of scraping artifacts.
    from horovod_tpu.goodput import ledger as goodput_ledger
    goodput_ledger.append_record(bench=result)
    if model_name != "resnet50":
        # Non-flagship measurements persist as artifacts so the scaling
        # projection can consume them (see _projected_efficiency).
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               f"BENCH_{model_name.upper()}.json"),
                  "w") as f:
            json.dump(result, f, indent=1)
    hvd.shutdown()
    return 0


# ---------------------------------------------------------------------------
# scaling harness (--scaling): weak scaling on virtual meshes + compile-only
# collective stats at large mesh shapes (BASELINE north star tracking)
# ---------------------------------------------------------------------------

# CPU-feasible shrink of the same workload (full ResNet-50 graph, small
# images): the point is the framework step's communication structure, not
# CPU throughput.
_SCALE_IMAGE = 32
_SCALE_BATCH_PER_DEV = 8

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16,
                "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
                "f8e4m3fnuz": 1, "f8e5m2fnuz": 1}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
_SHAPE_RE = re.compile(
    r"\b(pred|f8e4m3fn|f8e5m2|f8e4m3b11fnuz|f8e4m3fnuz|f8e5m2fnuz"
    r"|[sufc]\d+|bf16)\[([\d,]*)\]")


def _shape_bytes(typestr: str) -> int:
    """Total bytes of every HLO shape literal in ``typestr`` (tuple types
    sum all elements)."""
    nbytes = 0
    for dtype, dims in _SHAPE_RE.findall(typestr):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES.get(dtype, 4)
    return nbytes


def _hlo_collective_stats(hlo_text: str) -> dict:
    """Per-step collective op counts and result-byte volumes from (optimized)
    HLO text. Counts the op's RESULT shapes (for variadic/fused all-reduce:
    every tuple element), which is the data a ring moves once. Async forms
    count their ``-start`` op (the ``-done`` carries no new transfer);
    real-TPU compiles emit the async pairs."""
    stats = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*" + HLO_RESULT_TYPE + r"\s+([a-z-]+)\(", line)
        if not m:
            continue
        raw = m.group(1)
        op = raw[:-len("-start")] if raw.endswith("-start") else raw
        if op not in _COLLECTIVES:
            continue
        stats[op]["count"] += 1
        stats[op]["bytes"] += _shape_bytes(line.split(f" {raw}(", 1)[0])
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    stats["total_count"] = sum(v["count"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


def _build_scale_step(mode: str = "auto"):
    """``auto``: replicated params + sharded batch under plain jit — XLA's
    partitioner inserts the gradient reductions. ``fused``: explicit-axis
    DP through shard_map — gradient sync runs through the framework's
    in-graph fusion buffer (one all-reduce per dtype,
    parallel/distributed._sync_leaves_fused)."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.size()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, _SCALE_IMAGE, _SCALE_IMAGE, 3), jnp.bfloat16))
    variables = jax.tree.map(np.asarray, variables)
    if mode == "auto":
        optimizer = hvd.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), op=hvd.Average)
        step, state = build_step(model, optimizer, variables, mesh)
    else:
        from jax import lax
        from horovod_tpu.eager import shard_map
        from horovod_tpu.parallel.trainer import jit_step
        optimizer = hvd.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), op=hvd.Average, axis="hvd")

        def shard_step(state, x, y):
            params, batch_stats, opt_state = state

            def loss_fn(p):
                logits, upd = model.apply(
                    {"params": p, "batch_stats": batch_stats}, x,
                    train=True, mutable=["batch_stats"])
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
                return loss, upd["batch_stats"]

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # Keep BN running stats replica-identical (a few KB pmean).
            new_stats = jax.tree.map(lambda s: lax.pmean(s, "hvd"),
                                     new_stats)
            return (params, new_stats, opt_state), lax.pmean(loss, "hvd")

        step = jit_step(shard_map(
            shard_step, mesh=mesh, in_specs=(P(), P("hvd"), P("hvd")),
            out_specs=(P(), P())))
        repl = NamedSharding(mesh, P())
        params = jax.device_put(variables["params"], repl)
        batch_stats = jax.device_put(variables.get("batch_stats", {}), repl)
        state = (params, batch_stats, optimizer.init(params))
    rng = np.random.RandomState(0)
    batch = _SCALE_BATCH_PER_DEV * n
    data_sh = NamedSharding(mesh, P("hvd"))
    x = jax.device_put(
        jnp.asarray(rng.rand(batch, _SCALE_IMAGE, _SCALE_IMAGE, 3),
                    jnp.bfloat16), data_sh)
    y = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32), data_sh)
    return step, state, x, y, n


def _worker_mode() -> str:
    return "fused" if "fused" in sys.argv else "auto"


def _scaling_worker() -> int:
    """Measure the framework step's throughput at this process's device
    count (parent sets the virtual-mesh env)."""
    step, state, x, y, n = _build_scale_step(_worker_mode())
    ips, _ = measure(step, state, x, y, n_warmup=2, n_steps=8)
    print(json.dumps({"n": n, "img_s": round(ips, 2),
                      "img_s_per_dev": round(ips / n, 2)}))
    return 0


def _collectives_worker() -> int:
    """Compile-only: optimized-HLO collective stats at this device count
    (no execution — how the 256-mesh shape is analyzable without chips)."""
    mode = _worker_mode()
    step, state, x, y, n = _build_scale_step(mode)
    lowered = step.lower(state, x, y)
    try:
        hlo = lowered.compile().as_text()
        source = "optimized"
    except Exception:                      # huge mesh: fall back to lowered
        hlo = lowered.as_text()
        source = "lowered"
    stats = _hlo_collective_stats(hlo)
    stats.update({"n": n, "hlo": source, "mode": mode})
    print(json.dumps(stats))
    return 0


def _spawn(mode: str, n: int, variant: str = "auto",
           timeout: float = 1800.0) -> dict:
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), mode, variant],
        env=env, capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"{mode} n={n} failed:\n{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def scaling_main() -> int:
    weak = []
    for n in (1, 2, 4, 8):
        try:
            weak.append(_spawn("--scaling-worker", n, "fused"))
        except Exception as e:     # one failed run must not lose the rest
            weak.append({"n": n, "error": str(e)[-400:]})
    base = next((r["img_s_per_dev"] for r in weak if "img_s_per_dev" in r),
                None)
    for row in weak:
        # NOTE: virtual devices share one host CPU, so this efficiency is a
        # lower bound dominated by core contention, not ICI — the collective
        # volumes below are the hardware-relevant scaling evidence.
        if base and "img_s_per_dev" in row:
            row["efficiency"] = round(row["img_s_per_dev"] / base, 3)
    coll = []
    for n in (8, 64, 256):
        for variant in ("auto", "fused"):
            try:
                coll.append(_spawn("--collectives-worker", n, variant))
            except Exception as e:
                coll.append({"n": n, "mode": variant,
                             "error": str(e)[-400:]})
    # Ring property the >=90 % @256 target rests on: bytes moved per device
    # per step ~ constant in n (all-reduce ring moves 2(n-1)/n x payload).
    # The metric names the mesh sizes it actually compares — if the largest
    # compile failed, the ratio must not masquerade as the 256-dev number.
    fused = [c for c in coll
             if c.get("mode") == "fused" and c.get("total_bytes")]
    ratio, span = None, None
    if len(fused) >= 2:
        ratio = round(fused[-1]["total_bytes"] / fused[0]["total_bytes"], 3)
        span = f"{fused[0]['n']}_to_{fused[-1]['n']}dev"
    result = {"virtual_cpu_weak_scaling_DIAGNOSTIC_ONLY": {
                  "note": "virtual devices share ONE host CPU; these "
                          "efficiencies measure core contention, NOT "
                          "hardware scaling — the hardware claim is "
                          "projected_efficiency + collective_stats",
                  "rows": weak},
              "collective_stats": coll,
              "collective_bytes_growth": ratio,
              "collective_bytes_growth_span": span,
              "projected_efficiency": _projected_efficiency()}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SCALING.json")
    # hand-committed sections (chip measurements with provenance) ride
    # across regens: the cost-model rates HVD705 verdicts against, and
    # the DCN tier model
    if os.path.exists(path):
        with open(path) as f:
            prior = json.load(f)
        for section in ("dcn_tier_model", "cost_model_rates"):
            if section in prior:
                result[section] = prior[section]
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "metric": f"collective_bytes_growth_{span or 'unavailable'}",
        "value": ratio,
        "unit": "ratio",
        "vs_baseline": None,
        "weak_scaling_8dev_efficiency": weak[-1].get("efficiency"),
        "detail": "SCALING.json",
    }))
    return 0


# ---------------------------------------------------------------------------
# collective microbenchmark (--collectives): measured op cost vs message
# size on the chips this process can see (the NCCL-tests role,
# ref docs/benchmarks.rst measurement methodology)
# ---------------------------------------------------------------------------

# Ring-allreduce projection constants (stated assumptions, overridable by
# HVD_BENCH_ICI_GBPS / HVD_BENCH_ICI_HOP_US): v5e ICI is published as
# 1,600 Gbit/s aggregate per chip; a 1D ring drives one link pair in each
# direction, so the effective allreduce ring bandwidth per chip is taken
# as 100 GB/s, per-hop latency ~1 us. Single definition shared with the
# bucket auto-search scorer so both always use the same latency model.
from horovod_tpu.analysis.rules_ir import HLO_RESULT_TYPE  # noqa: E402
from horovod_tpu.autotune import (  # noqa: E402
    ICI_HOP_LATENCY_S, ICI_RING_GBPS)


def collectives_main() -> int:
    """Measure allreduce/allgather/reducescatter cost vs message size
    through the framework's in-graph path, iterations chained inside one
    executable (unchained loops would measure dispatch, not the op). On a
    single chip the
    collective leg is local — the numbers are the framework+memory floor
    and the ICI term is analytic (projection in SCALING.json); on a real
    multi-chip mesh the same harness measures true ICI cost."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.ops import collectives as C

    require_chip("--collectives")
    hvd.init()
    n = hvd.size()
    axis = "hvd"
    mesh = hvd.mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.eager import shard_map

    sizes = [1 << k for k in range(10, 29, 2)]      # 1 KB .. 256 MB
    n_iter = 20
    rows = []
    for op_name in ("allreduce", "allgather", "reducescatter"):
        for nbytes in sizes:
            if op_name == "allgather" and nbytes * n > (1 << 29):
                continue                            # gathered output cap
            elems = nbytes // 4
            if op_name == "reducescatter" and elems % n:
                continue
            x = jnp.zeros((elems,), jnp.float32)
            x = jax.device_put(x, NamedSharding(mesh, P()))

            def body_op(v):
                if op_name == "allreduce":
                    return C.allreduce(v, axis=axis)
                if op_name == "allgather":
                    return C.allgather(v, axis=axis)[:v.shape[0]]
                return jnp.pad(C.reducescatter(v, axis=axis),
                               (0, elems - elems // n))

            def chained(v):
                def body(i, acc):
                    out = body_op(acc * 0.5)
                    return out
                return jax.lax.fori_loop(0, n_iter, body, v)

            fn = jax.jit(shard_map(chained, mesh=mesh, in_specs=P(),
                                   out_specs=P()))
            r = fn(x)
            jax.block_until_ready(r)
            t0 = time.perf_counter()
            r = fn(x)
            float(jnp.sum(r))                       # true completion barrier
            dt = (time.perf_counter() - t0) / n_iter
            # NCCL-tests conventions: algbw = payload/time; busbw scales by
            # the ring factor so the number is comparable across world sizes.
            factor = {"allreduce": 2 * (n - 1) / n,
                      "allgather": (n - 1) / n,
                      "reducescatter": (n - 1) / n}[op_name] if n > 1 else 1.0
            rows.append({
                "op": op_name, "bytes": nbytes, "n_devices": n,
                "time_us": round(dt * 1e6, 2),
                "algbw_gb_s": round(nbytes / dt / 1e9, 3),
                "busbw_gb_s": round(factor * nbytes / dt / 1e9, 3),
            })
    if n == 1:
        # Single-device rows are NOT collective bandwidth (VERDICT r5
        # Weak 4): flag them so the artifact can never masquerade as ICI
        # evidence.
        for r in rows:
            r["single_device_floor"] = True
    out = {"device_kind": getattr(jax.devices()[0], "device_kind", "?"),
           "n_devices": n,
           "SINGLE_DEVICE_FLOOR_ONLY": n == 1,
           "note": ("single-chip rows measure the framework+HBM floor of "
                    "the collective path (no ICI traffic exists on one "
                    "chip; each row carries single_device_floor=true); "
                    "multi-chip runs of the same harness measure real "
                    "ICI"),
           "rows": rows}
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "COLLECTIVES.json"), "w") as f:
        json.dump(out, f, indent=1)
    big = [r for r in rows if r["op"] == "allreduce"][-1]
    print(json.dumps({
        "metric": "allreduce_floor_algbw",
        "value": big["algbw_gb_s"], "unit": "GB/s",
        "vs_baseline": None, "bytes": big["bytes"],
        "n_devices": n, "detail": "COLLECTIVES.json"}))
    hvd.shutdown()
    return 0


def _projected_efficiency() -> dict:
    """Analytic ring-allreduce weak-scaling projection for the fused
    framework step (BASELINE >=90 % @256 target). Combines the measured
    single-chip step time (the newest BENCH_r<N>.json beside this file;
    none is committed — no chip record yet), the measured fused collective
    payload (optimized-HLO stats in this file's --collectives-worker), and
    stated ICI assumptions — replacing the meaningless virtual-CPU-mesh
    efficiency rows as the hardware claim."""
    here = os.path.dirname(os.path.abspath(__file__))
    step_s, img_s, batch = None, None, None
    bench_files = [(int(m.group(1)), name)
                   for name in os.listdir(here)
                   for m in [re.match(r"BENCH_r(\d+)\.json", name)] if m]
    for _, name in sorted(bench_files, reverse=True):
        try:
            b = json.load(open(os.path.join(here, name)))
            parsed = b.get("parsed", b)
            img_s = float(parsed["value"])
            batch = int(parsed.get("batch_per_chip", 256))
            step_s = batch / img_s
            break
        except Exception:
            continue
    if step_s is None:
        return {"error": "no chip record: no BENCH_r<N>.json with a "
                         "measured step time beside bench.py"}

    # Measured hideable-compute fraction from the TPU compiler's own
    # dependence graph (bench.py --overlap-report, OVERLAP.json): with
    # bucketed gradient sync (HOROVOD_GRADIENT_BUCKET_BYTES), this
    # payload-weighted share of conv compute is INDEPENDENT of the
    # in-flight gradient collective and can execute during it; with the
    # single fused all-reduce it is 0 (every conv feeds the collective).
    hideable = 0.0
    try:
        ov = json.load(open(os.path.join(here, "OVERLAP.json")))
        cfgs = ov["configs"]
        bb = [k for k in cfgs if k != "0"]
        if bb:
            hideable = float(
                cfgs[bb[0]]["hideable_conv_fraction_weighted"])
    except FileNotFoundError:
        pass
    except Exception as e:        # malformed artifact: degrade, loudly
        print(f"bench.py: ignoring unreadable OVERLAP.json ({e!r})",
              file=sys.stderr)

    # Fraction of the step that is backward compute (fwd+bwd ~= 3x fwd).
    _BWD_FRACTION = 2.0 / 3.0

    def ring_rows(step_s, payload):
        rows = []
        for n in (8, 64, 256):
            t_ring = 2 * (n - 1) / n * payload / (ICI_RING_GBPS * 1e9)
            t_lat = 2 * (n - 1) * ICI_HOP_LATENCY_S
            t_comm = t_ring + t_lat
            # Hidden comm is capped by the independent compute that
            # actually exists to run during the collectives: the hideable
            # fraction of backward time — not an uncapped share of comm.
            hidden = min(t_comm * hideable,
                         step_s * _BWD_FRACTION * hideable)
            exposed = t_comm - hidden
            rows.append({
                "n_chips": n,
                "t_step_ms": round(step_s * 1e3, 2),
                "t_allreduce_ms": round(t_comm * 1e3, 3),
                "efficiency_no_overlap": round(
                    step_s / (step_s + t_comm), 4),
                "efficiency_bucketed_overlap": round(
                    step_s / (step_s + exposed), 4),
                "efficiency_full_overlap": 1.0 if t_comm < step_s
                else round(step_s / t_comm, 4),
            })
        return rows

    payload = 102.4e6        # fused gradient allreduce bytes/step/device
    rows = ring_rows(step_s, payload)
    # VGG-16: the reference table's hard case (68 % @512,
    # docs/benchmarks.rst:13-14) — ~138M params = 554 MB f32 gradient
    # payload. Step time comes from the BENCH_VGG16.json artifact that
    # `python bench.py vgg16` writes after measuring on the real chip.
    vgg16 = None
    try:
        vb = json.load(open(os.path.join(here, "BENCH_VGG16.json")))
    except FileNotFoundError:
        vb = None                      # not measured yet: section omitted
    if vb is not None:
        # Any OTHER problem (malformed artifact, zero value) must surface,
        # not silently drop the evidence section PARITY points at.
        vgg_step = vb["batch_per_chip"] / vb["value"]
        vgg16 = {"rows": ring_rows(vgg_step, 138.4e6 * 4),
                 "payload_bytes_per_step_per_device": 138.4e6 * 4,
                 "step_time_source":
                     f"measured vgg16 step ({vb['batch_per_chip']} img @ "
                     f"{vb['value']} img/s, BENCH_VGG16.json)",
                 "hideable_fraction_note":
                     "hideable fraction was measured on the ResNet-50 "
                     "dependence graph and applied here as a PROXY; the "
                     "backward-compute cap above still bounds it"}
    return {
        "assumptions": {
            "ici_ring_gb_s_per_chip": ICI_RING_GBPS,
            "ici_hop_latency_us": ICI_HOP_LATENCY_S * 1e6,
            "payload_bytes_per_step_per_device": payload,
            "payload_source": "SCALING.json collective_stats (fused mode; "
                              "bytes flat 8->256 dev. The TPU pipeline "
                              "splits this payload into ~5 bucketed "
                              "all-reduces — same bytes, overlap-capable "
                              "dataflow, OVERLAP.json; the CPU-derived "
                              "stats here show the combiner-merged form)",
            "step_time_source": f"measured single-chip step ({batch} "
                                f"img @ {img_s} img/s)",
            "hideable_compute_fraction": hideable,
            "hideable_source": "OVERLAP.json (bench.py --overlap-report): "
                               "TPU-compiler dependence graph, payload-"
                               "weighted conv fusions independent of each "
                               "bucketed gradient all-reduce. EVIDENCE "
                               "LEVEL: compile-schedule position, not "
                               "observed concurrency — the bucketing "
                               "guarantees the dataflow precondition an "
                               "async backend needs (PERF.md r5 'Limits, "
                               "honestly')",
            "model": "ring allreduce 2(n-1)/n * S / B + 2(n-1) * hop_lat; "
                     "no-overlap = all comm exposed; bucketed-overlap = "
                     "comm x (1 - measured hideable fraction) exposed "
                     "(HOROVOD_GRADIENT_BUCKET_BYTES buckets); "
                     "full-overlap = ideal ceiling",
        },
        "rows": rows,
        "vgg16": vgg16,
    }


def project_main() -> int:
    """--project: refresh ONLY the projected_efficiency section of
    SCALING.json from the current BENCH artifacts (cheap — no weak-scaling
    reruns or large-mesh compiles)."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "SCALING.json")
    data = json.load(open(path)) if os.path.exists(path) else {}
    data["projected_efficiency"] = _projected_efficiency()
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    print(json.dumps({"metric": "projection_refreshed", "value": 1,
                      "unit": "", "vs_baseline": None,
                      "detail": "SCALING.json"}))
    return 0


# ---------------------------------------------------------------------------
# pallas streaming-bandwidth probe (--pallas-bandwidth): device-timed pure
# copy through a pallas_call vs an XLA elementwise pass, by block size —
# the experiment that closes the fused-conv+BN question (PERF.md r5:
# the deficit is a toolchain DMA ceiling, not kernel block scheduling)
# ---------------------------------------------------------------------------

def pallas_bandwidth_main() -> int:
    import glob
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:
        print("bench.py --pallas-bandwidth needs the TF xplane protobufs "
              "(set PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python)",
              file=sys.stderr)
        return 2

    M, N = 131072, 1024       # 256 MB bf16: HBM-resident on both arms
    n_it = 8
    x = jnp.ones((M, N), jnp.bfloat16)

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def pallas_copy(bm, semantics):
        def f(v):
            return pl.pallas_call(
                copy_kernel, grid=(M // bm,),
                in_specs=[pl.BlockSpec((bm, N), lambda m: (m, 0))],
                out_specs=pl.BlockSpec((bm, N), lambda m: (m, 0)),
                out_shape=jax.ShapeDtypeStruct((M, N), v.dtype),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=(semantics,)))(v)
        return f

    def xla_pass(v):
        # data-dependent scalar so XLA cannot algebraically collapse the
        # loop (it folds constant-scale chains into the final reduce)
        return v * (v[0, 0] * jnp.bfloat16(0.001) + jnp.bfloat16(1.0))

    def device_ms(fn):
        @jax.jit
        def chained(v):
            return jnp.sum(jax.lax.fori_loop(
                0, n_it, lambda i, a: fn(a), v).astype(jnp.float32))
        float(chained(x))
        d = tempfile.mkdtemp()
        try:
            jax.profiler.start_trace(d)
            float(chained(x))
            jax.profiler.stop_trace()
            traces = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
            if not traces:
                raise RuntimeError(
                    "jax.profiler produced no xplane trace — cannot "
                    "device-time the bandwidth probe")
            xs_ = xplane_pb2.XSpace()
            xs_.ParseFromString(open(traces[0], "rb").read())
            total = 0
            for p in xs_.planes:
                if "TPU" not in p.name:
                    continue
                for line in p.lines:
                    for ev in line.events:
                        nm = p.event_metadata[ev.metadata_id].name
                        # The streamed pass per iteration only — the
                        # one-shot closing sum would inflate every arm
                        # by ~1 extra array read / n_it.
                        if "reduce" in nm or "convert" in nm:
                            continue
                        if any(k in nm for k in ("fusion", "copy",
                                                 "custom-call",
                                                 "multiply")):
                            total += ev.duration_ps
                break
        finally:
            shutil.rmtree(d, ignore_errors=True)
        if not total:
            raise RuntimeError(
                "no matching device events in the xplane trace (profiler "
                "op naming changed?) — bandwidth probe cannot report")
        return total / 1e9 / n_it

    nbytes = 2 * M * N * 2    # read + write, bf16
    rows = []
    ms = device_ms(xla_pass)
    rows.append({"impl": "xla_elementwise", "ms": round(ms, 3),
                 "gb_s": round(nbytes / (ms / 1e3) / 1e9, 1)})
    # bm capped at 2048: (4096,1024)-bf16 blocks double-buffered
    # exceed the 16 MB scoped-VMEM limit at this array size
    for bm in (512, 1024, 2048):
        ms = device_ms(pallas_copy(bm, "arbitrary"))
        rows.append({"impl": f"pallas_copy_bm{bm}", "ms": round(ms, 3),
                     "gb_s": round(nbytes / (ms / 1e3) / 1e9, 1)})
    ms = device_ms(pallas_copy(2048, "parallel"))
    rows.append({"impl": "pallas_copy_bm2048_parallel",
                 "ms": round(ms, 3),
                 "gb_s": round(nbytes / (ms / 1e3) / 1e9, 1)})
    ratio = rows[1]["gb_s"] / rows[0]["gb_s"] if rows[0]["gb_s"] else None
    print(json.dumps({"metric": "pallas_stream_vs_xla_bandwidth",
                      "value": round(ratio, 3) if ratio else None,
                      "unit": "ratio", "vs_baseline": None,
                      "rows": rows}))
    return 0


# ---------------------------------------------------------------------------
# divergence-check overhead (--divergence-overhead): ms/flush of the
# multi-controller digest exchange over the REAL jax.distributed KV at
# 2/4/8 processes (the hot-path cost HOROVOD_DIVERGENCE_CHECK_EVERY
# amortizes — ref response_cache.h:107 fast-path rationale)
# ---------------------------------------------------------------------------

_DIVERGENCE_WORKER = r"""
import sys, time, json
import jax
jax.config.update("jax_platforms", "cpu")
idx, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=n, process_id=idx)
from horovod_tpu.utils.kvstore import distributed_kv
from horovod_tpu.ops.divergence import DivergenceChecker
from horovod_tpu.ops.coordinator import Entry
import numpy as np

kv = distributed_kv(site="divergence")
c = DivergenceChecker(kv, idx, n, prefix="bench/divo")
e = Entry(name="g", op_type="allreduce",
          x=np.ones((1024,), np.float32), handle=None)
warm, iters = 5, 50
for i in range(warm):
    c.observe(i + 1, [e])
t0 = time.perf_counter()
for i in range(iters):
    c.observe(warm + i + 1, [e])
dt = (time.perf_counter() - t0) / iters * 1e3
if idx == 0:
    print(json.dumps({"n": n, "ms_per_flush": round(dt, 3),
                      "checks": c.checks}), flush=True)
"""


def divergence_overhead_main() -> int:
    import socket
    import subprocess

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    here = os.path.dirname(os.path.abspath(__file__))
    rows = []
    for n in (2, 4, 8):
        port = free_port()
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["HOROVOD_DIVERGENCE_CHECK_EVERY"] = "1"
        env["HOROVOD_DIVERGENCE_CHECK_MAX_INTERVAL"] = "1"  # measure base
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        procs = [subprocess.Popen(
            [sys.executable, "-c", _DIVERGENCE_WORKER, str(i), str(n),
             str(port)], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
            for i in range(n)]
        try:
            out, err = procs[0].communicate(timeout=300)
            for p in procs[1:]:
                p.wait(timeout=60)
            lines = out.strip().splitlines()
            if not lines:
                raise RuntimeError(
                    f"divergence-overhead worker 0 (n={n}) printed "
                    f"nothing; stderr tail: {err[-800:]}")
            rows.append(json.loads(lines[-1]))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    print(json.dumps({
        "metric": "divergence_check_ms_per_flush",
        "value": rows[-1]["ms_per_flush"], "unit": "ms (8 proc)",
        "vs_baseline": None, "rows": rows}))
    return 0


# ---------------------------------------------------------------------------
# transformer flagship benchmark (`bench.py transformer`): TransformerLM
# training tokens/s + MFU on the real chip — the workload class TPUs run in
# 2026 (ref benchmark-doc pattern docs/benchmarks.rst:20-43, applied to the
# flagship model the dryrun compiles)
# ---------------------------------------------------------------------------

def transformer_main() -> int:
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.parallel.trainer import make_transformer_train_step

    require_chip("transformer")
    peak = peak_flops(jax.devices()[0])
    hvd.init()
    mesh = hvd.mesh()
    n_chips = hvd.size()

    # ~270M-param LM (GPT-2-medium class): large enough that matmuls fill
    # the MXU, small enough that params+momentum+grads fit one v5e chip.
    # scan_unroll=n_layers: full unroll deletes the scan-carry layout
    # copies, measured +17% on v5e (PERF.md r5; partial unroll is worse
    # than either extreme).
    base = dict(vocab_size=32768, d_model=1024, n_heads=16, head_dim=64,
                n_layers=16, d_ff=4096, max_seq=2048, scan_unroll=16,
                dtype=jnp.bfloat16, dp_axis="hvd")
    seq = 2048
    from horovod_tpu.ops.blockwise_ce import default_block
    ce_block_default = default_block()
    rng = np.random.RandomState(0)
    optimizer = optax.sgd(0.01, momentum=0.9)

    # Config sweep: selective MLP recompute (mlp_recompute=True, the r6
    # default — recomputes only the two d_ff-wide MLP activations, removing
    # their ~20 ms/step of saved-activation HBM traffic) vs the r5
    # save-everything config, vs full-layer remat (measured LOSING at every
    # batch in r5 — kept in the sweep as the guard rail).
    from horovod_tpu.ops.pallas.flash_attention import compiled_kernels
    best = None    # (tok/s, (remat, mlp_recompute), batch_per_chip, kernels)
    for remat, mlp_recompute in ((False, True), (False, False),
                                 (True, True)):
        for batch_per_chip in (4, 8, 16):
            cfg = TransformerConfig(remat=remat,
                                    mlp_recompute=mlp_recompute, **base)
            try:
                init_fn, train_step = make_transformer_train_step(
                    cfg, optimizer, mesh)
                state = init_fn(jax.random.PRNGKey(0))
                B = batch_per_chip * n_chips
                from jax.sharding import NamedSharding, PartitionSpec as P
                sh = NamedSharding(mesh, P("hvd"))
                tokens = jax.device_put(
                    jnp.asarray(rng.randint(0, base["vocab_size"],
                                            (B, seq)), jnp.int32), sh)
                labels = jax.device_put(
                    jnp.asarray(rng.randint(0, base["vocab_size"],
                                            (B, seq)), jnp.int32), sh)
                # One AOT compile: the executable that is inspected for
                # its kernels is the one the timed loop dispatches.
                step = train_step.lower(state, tokens, labels).compile()
                kernels = compiled_kernels(step.as_text())
                for _ in range(2):
                    state, loss = step(state, tokens, labels)
                float(loss)
                t0 = time.perf_counter()
                n_steps = 10
                for _ in range(n_steps):
                    state, loss = step(state, tokens, labels)
                final = float(loss)
                dt = time.perf_counter() - t0
                assert np.isfinite(final), final
                toks = B * seq * n_steps / dt
                if best is None or toks > best[0]:
                    best = (toks, (remat, mlp_recompute), batch_per_chip,
                            kernels)
            except jax.errors.JaxRuntimeError as e:
                # Out of device memory: this config does not fit here,
                # skip larger batches. Anything else is a real failure
                # and must surface.
                s = str(e)
                if "RESOURCE_EXHAUSTED" not in s:
                    raise
                print(f"bench.py transformer: remat={remat} "
                      f"batch={batch_per_chip} skipped ({s[:80]!r})",
                      file=sys.stderr)
                break
    if best is None:
        print("bench.py transformer: nothing fit in memory",
              file=sys.stderr)
        return 1
    toks, (remat, mlp_recompute), batch_per_chip, kernels = best

    # Model FLOPs (MFU convention: no remat/recompute FLOPs counted).
    # 6*P per token for the dense path + 12*L*S*d_attn per token for
    # causal attention scores/values (PaLM appendix B accounting with the
    # causal 1/2 already applied -> 6*L*S*d_attn).
    cfg = TransformerConfig(remat=remat, mlp_recompute=mlp_recompute,
                            **base)
    d_attn = cfg.n_heads * cfg.head_dim
    n_params = (cfg.vocab_size * cfg.d_model                 # embedding
                + cfg.n_layers * (4 * cfg.d_model * d_attn
                                  + 2 * cfg.d_model * cfg.d_ff
                                  + 2 * cfg.d_model)
                + cfg.d_model + cfg.d_model * cfg.vocab_size)
    flops_per_token = 6 * n_params + 6 * cfg.n_layers * seq * d_attn
    mfu = (toks / n_chips) * flops_per_token / peak

    result = {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(toks / n_chips, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,     # reference publishes no LM numbers
        "mfu": round(mfu, 4),
        "params_millions": round(n_params / 1e6, 1),
        "seq": seq,
        "batch_per_chip": batch_per_chip,
        "remat": remat,
        "mlp_recompute": mlp_recompute,
        # NOTE: ce_block_vocab=0 is a meaningful value (explicit unfused
        # path) — only None falls back to the knob default.
        "ce": ("blockwise" if (ce_block_default if cfg.ce_block_vocab is None
                               else cfg.ce_block_vocab) else "unfused"),
        "ce_block_vocab": (ce_block_default if cfg.ce_block_vocab is None
                           else cfg.ce_block_vocab),
        # Mosaic-compiled flash kernels found in the measured step's HLO
        # (fwd and both bwd kernels, or the step took the jnp path).
        "flash_attention": {"hvd_flash_fwd", "hvd_flash_bwd_dq",
                            "hvd_flash_bwd_dkv"} <= set(kernels),
        "chip": jax.devices()[0].device_kind,
    }
    print(json.dumps(result))
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_TRANSFORMER.json"), "w") as f:
        json.dump(result, f, indent=1)
    hvd.shutdown()
    return 0


# ---------------------------------------------------------------------------
# overlap report (--overlap-report): HLO-schedule evidence that bucketed
# gradient sync (HOROVOD_GRADIENT_BUCKET_BYTES) breaks the single terminal
# all-reduce into per-bucket collectives interleaved with backward compute
# ---------------------------------------------------------------------------

def verify_report_main() -> int:
    """``bench.py --verify-report``: run the IR-tier step verifier
    (hvd.verify_step, HVD5xx — docs/analysis.md) over the flagship
    transformer and ResNet DP training steps on the hardware-free
    8-device virtual CPU mesh, emit the expected-collectives manifest +
    findings + collective-order fingerprint per workload to VERIFY.json,
    and exit non-zero on any non-baselined finding (the CI ``hvdverify``
    job's contract: a sharding/reduction/donation regression in either
    flagship step fails the build before it ever reaches a chip).

    The model shapes are scaled down from the benchmark configs (CI
    compiles on CPU), but the steps are built by the SAME constructors
    training uses — make_transformer_train_step and the explicit-axis
    DistributedOptimizer shard_map step — so the collective structure
    being verified is the production one.
    """
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.analysis.engine import load_baseline, split_new
    from horovod_tpu.analysis.ir import verify_report
    from horovod_tpu.config import knobs
    from horovod_tpu.eager import shard_map
    from horovod_tpu.models import ResNet18
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops import fusion
    from horovod_tpu.parallel.trainer import (
        TrainState, jit_step, make_transformer_train_step)

    devs = np.array(jax.devices())
    out = {"n_devices": int(devs.size),
           "platform": jax.devices()[0].platform,
           "workloads": {}}
    findings = []

    # ---- flagship transformer DP step (trainer-built) -------------------
    mesh = Mesh(devs.reshape(devs.size), ("dp",))
    cfg = tfm.TransformerConfig(
        vocab_size=2048, d_model=256, n_heads=4, head_dim=64, n_layers=4,
        d_ff=1024, max_seq=256, dtype=jnp.bfloat16, dp_axis="dp")
    optimizer = optax.sgd(0.01, momentum=0.9)
    _, train_step = make_transformer_train_step(cfg, optimizer, mesh)
    params = jax.eval_shape(lambda: tfm.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(lambda: optimizer.init(params))
    state = TrainState(jax.ShapeDtypeStruct((), jnp.int32), params,
                       opt_state)
    toks = jax.ShapeDtypeStruct((2 * devs.size, 256), jnp.int32)
    grad_sizes = fusion.leaf_sizes(params)
    # trainer.sync_gradients hands each leaf to psum as it is and the
    # compiler's all-reduce combiner merges them (no bucketing on this
    # path): the budget is the bucket_bytes=0 schedule's, one all-reduce
    # of at most every gradient byte.
    tfm_manifest = fusion.expected_manifest(grad_sizes, 0)
    fs, report = verify_report(
        train_step, (state, toks, toks), mesh=mesh, expected=tfm_manifest,
        name="flagship-transformer-dp", tag="verify-report-transformer")
    findings += fs
    out["workloads"]["transformer"] = report

    # ---- compressed flagship variant (wire fp8 + optimizer-in-epilogue)
    # The hvdwire acceptance gates, asserted structurally on the virtual
    # mesh: (a) every gradient-sized reduction in the traced step carries
    # the wire dtype — NO full-precision (>=32-bit) gradient all-reduce
    # survives into the optimized HLO (scalar loss pmean / fp8 amax
    # exchanges are exempt below 4 KiB); (b) the bucketed-apply step has
    # NO whole-model optimizer pass (the unfused twin's
    # 'hvd_unfused_apply' scope) — the update runs in the per-bucket
    # 'hvd_bucket<k>_apply' epilogues; (c) the auto-declared manifest
    # (expect_compression/wire_dtype) passes HVD505 with no hand-written
    # entries. fp8_e4m3 rather than bf16 keeps gate (a) meaningful on
    # CPU, whose float-normalization pass upcasts bf16 collectives to
    # f32 (fp8 normalizes to f16 — still sub-32-bit); the traced-jaxpr
    # dtype evidence in the report is exact on every platform.
    from horovod_tpu.analysis import rules_ir
    from horovod_tpu.parallel.distributed import (
        EpilogueSGD, distributed_apply)
    from horovod_tpu.parallel.trainer import (
        make_transformer_train_step_fused)
    knobs.set_override("HOROVOD_GRADIENT_COMPRESSION", "fp8_e4m3")
    try:
        apply_opt = distributed_apply(
            EpilogueSGD(0.01, momentum=0.9),
            sync_axes=tfm.grad_sync_axes(cfg), mesh=mesh)
        _, comp_step = make_transformer_train_step_fused(
            cfg, apply_opt, mesh)
        comp_state = TrainState(
            jax.ShapeDtypeStruct((), jnp.int32), params,
            jax.eval_shape(apply_opt.init, params))
        bb = knobs.get("HOROVOD_GRADIENT_BUCKET_BYTES")
        bb = bb if isinstance(bb, int) else 25 * 1024 * 1024
        comp_manifest = fusion.expected_manifest(grad_sizes, bb)
        fs, report = verify_report(
            comp_step, (comp_state, toks, toks), mesh=mesh,
            expected=comp_manifest,
            name="flagship-transformer-dp-compressed",
            tag="verify-report-transformer-compressed")
        findings += fs
        gate_errors = []
        wide = rules_ir.wide_gradient_allreduces(
            report["collectives"], 4096)
        if wide:
            gate_errors.append(
                f"{len(wide)} full-precision gradient all-reduce(s) in "
                f"the compressed step's optimized HLO: "
                f"{[e['shape'] for e in wide]}")
        wrong_wire = [r for r in report["reduction_dtypes"]
                      if r["size"] * 4 >= 4096
                      and r["dtype"] != "float8_e4m3fn"]
        if wrong_wire:
            gate_errors.append(
                f"{len(wrong_wire)} gradient-sized traced reduction(s) "
                f"not in the fp8 wire dtype: "
                f"{sorted({r['dtype'] for r in wrong_wire})}")
        if report["apply_scopes"]["unfused"]:
            gate_errors.append(
                "the bucketed-apply step still carries a whole-model "
                "optimizer pass (hvd_unfused_apply scope present)")
        if not report["apply_scopes"]["bucket"]:
            gate_errors.append(
                "no hvd_bucket<k>_apply epilogue scopes in the "
                "bucketed-apply step's HLO")
        report["wire_gates"] = {
            "wide_gradient_allreduces": len(wide),
            "non_wire_gradient_reductions": len(wrong_wire),
            "errors": gate_errors,
        }
        out["workloads"]["transformer_compressed"] = report
    finally:
        knobs.clear_override("HOROVOD_GRADIENT_COMPRESSION")
    if gate_errors:
        for msg in gate_errors:
            print(f"hvdwire gate: {msg}", file=sys.stderr)
        out["wire_gate_failures"] = gate_errors

    # ---- tiered flagship variant (DCN two-level + slow-tier fp8) --------
    # The hvdtier acceptance gates on the virtual 2-slice mesh
    # (docs/hierarchical.md): (a) the per-tier manifest is auto-declared
    # and ENFORCED — per-bucket reduce-scatter / cross-slice all-reduce /
    # all-gather budgets, so an undeclared gather is an HVD502 finding;
    # (b) with compression declared, NO >=32-bit gradient collective
    # crosses the DCN axis — every gradient-sized traced reduction whose
    # axes include hvd_dcn carries the fp8 wire dtype, and the optimized
    # HLO has no wide all-reduce at all (the ICI stages are reduce-
    # scatter/all-gather, full-width by design: slow-tier-only
    # compression); (c) the per-stage scopes (_rs/_xdcn/_ag) survive
    # into the compiled HLO so profile attribution can split time per
    # tier.
    from horovod_tpu.runtime.topology import DCN_AXIS
    tier_gate_errors = []
    if devs.size < 4:
        # 2 virtual slices need >= 2 ranks per slice for the tier to be
        # a tier at all; a single-device sandbox skips the variant (the
        # CI hvdverify job always runs the 8-device virtual mesh and
        # asserts the workload is present).
        out["workloads"]["transformer_tiered"] = {
            "skipped": f"{devs.size} device(s) < 4 — no virtual-slice "
                       f"tier possible"}
    else:
        knobs.set_override("HOROVOD_DCN_SCHEDULE", "two_level")
        knobs.set_override("HOROVOD_GRADIENT_COMPRESSION", "fp8_e4m3")
        knobs.set_override("HOROVOD_GRADIENT_ERROR_FEEDBACK", "0")
        try:
            n_slices = 2
            n_ici = devs.size // n_slices
            mesh_t = Mesh(devs.reshape(n_slices, n_ici),
                          (DCN_AXIS, "hvd"))
            # in-slice loss reduction (dp_axis="hvd"); per-slice mean
            # losses and gradients agree up to the cross-slice average,
            # which the AVERAGE sync over BOTH axes supplies — the
            # standard multi-slice DP construction.
            import dataclasses as _dc
            cfg_t = _dc.replace(cfg, dp_axis="hvd")
            opt_t = hvd.DistributedOptimizer(
                optax.sgd(0.01, momentum=0.9), op=hvd.Average,
                axis=(DCN_AXIS, "hvd"))

            def tier_step(params, opt_state, tokens, labels):
                loss, grads = jax.value_and_grad(
                    lambda p: tfm.loss_fn(cfg_t, p, tokens,
                                          labels))(params)
                updates, opt_state = opt_t.update(grads, opt_state,
                                                  params)
                params = optax.apply_updates(params, updates)
                return params, opt_state, lax.pmean(loss,
                                                    (DCN_AXIS, "hvd"))

            tier_fn = jax.jit(shard_map(
                tier_step, mesh_t,
                in_specs=(P(), P(), P((DCN_AXIS, "hvd")),
                          P((DCN_AXIS, "hvd"))),
                out_specs=(P(), P(), P())),
                donate_argnums=(0, 1))
            opt_state_t = jax.eval_shape(lambda: opt_t.init(params))
            tier_manifest = fusion.expected_manifest(
                grad_sizes, bb, dcn={"ici_world": n_ici,
                                     "dcn_world": n_slices})
            fs, report = verify_report(
                tier_fn, (params, opt_state_t, toks, toks), mesh=mesh_t,
                expected=tier_manifest,
                name="flagship-transformer-dp-tiered",
                tag="verify-report-transformer-tiered")
            findings += fs
            if not (report["manifest"] or {}).get("tiers"):
                tier_gate_errors.append(
                    "the tiered variant's manifest carries no per-tier "
                    "declaration (expected_manifest dcn= block missing)")
            kinds = {e["kind"] for e in report["collectives"]}
            for want in ("reduce-scatter", "all-gather"):
                if want not in kinds:
                    tier_gate_errors.append(
                        f"no {want} in the tiered step's optimized HLO "
                        f"— the two-level schedule did not engage")
            wide = rules_ir.wide_gradient_allreduces(
                report["collectives"], 4096)
            if wide:
                tier_gate_errors.append(
                    f"{len(wide)} full-precision all-reduce(s) in the "
                    f"tiered step's optimized HLO: "
                    f"{[e['shape'] for e in wide]}")
            wrong_dcn = [r for r in report["reduction_dtypes"]
                         if DCN_AXIS in r["axes"]
                         and r["size"] * 4 >= 4096
                         and r["dtype"] != "float8_e4m3fn"]
            if wrong_dcn:
                tier_gate_errors.append(
                    f"{len(wrong_dcn)} gradient-sized cross-DCN traced "
                    f"reduction(s) not in the declared fp8 wire dtype: "
                    f"{sorted({r['dtype'] for r in wrong_dcn})}")
            report["tier_gates"] = {
                "collective_kinds": sorted(kinds),
                "wide_gradient_allreduces": len(wide),
                "non_wire_cross_dcn_reductions": len(wrong_dcn),
                "errors": tier_gate_errors,
            }
            out["workloads"]["transformer_tiered"] = report
        finally:
            knobs.clear_override("HOROVOD_DCN_SCHEDULE")
            knobs.clear_override("HOROVOD_GRADIENT_COMPRESSION")
            knobs.clear_override("HOROVOD_GRADIENT_ERROR_FEEDBACK")
    if tier_gate_errors:
        for msg in tier_gate_errors:
            print(f"hvdtier gate: {msg}", file=sys.stderr)
        out["tier_gate_failures"] = tier_gate_errors

    # ---- ResNet-18 DP step (explicit-axis DistributedOptimizer) ---------
    mesh_r = Mesh(devs.reshape(devs.size), ("hvd",))
    model = ResNet18(num_classes=100, dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, 64, 3), jnp.bfloat16)))
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   op=hvd.Average, axis="hvd")

    def shard_step(state, x, y):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, upd["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_stats = jax.tree.map(lambda s: lax.pmean(s, "hvd"), new_stats)
        return (params, new_stats, opt_state), lax.pmean(loss, "hvd")

    step = jit_step(shard_map(shard_step, mesh_r,
                              in_specs=(P(), P("hvd"), P("hvd")),
                              out_specs=(P(), P())))
    rparams = variables["params"]
    bstats = variables.get("batch_stats", {})
    ropt_state = jax.eval_shape(lambda: opt.init(rparams))
    x = jax.ShapeDtypeStruct((2 * devs.size, 64, 64, 3), jnp.bfloat16)
    y = jax.ShapeDtypeStruct((2 * devs.size,), jnp.int32)
    rsizes = fusion.leaf_sizes(rparams)
    bb = knobs.get("HOROVOD_GRADIENT_BUCKET_BYTES")
    bb = bb if isinstance(bb, int) else 25 * 1024 * 1024
    res_manifest = fusion.expected_manifest(rsizes, bb)
    fs, report = verify_report(
        step, ((rparams, bstats, ropt_state), x, y), mesh=mesh_r,
        expected=res_manifest, name="resnet18-dp",
        tag="verify-report-resnet")
    findings += fs
    out["workloads"]["resnet"] = report

    # ---- serving executables (prefill / decode / spec-verify) -----------
    # The serve engine's three step bodies, compiled exactly as
    # engine._adopt does (plain jit, pages donated), verified against a
    # ZERO-budget manifest: continuous-batching decode must stay free of
    # wide collectives — any >=1 MiB partitioner-inserted gather in a
    # latency-critical decode step is an HVD502 finding, and dropping
    # the page donation (the engine holds the only live copy) is an
    # HVD504 finding.
    import functools
    from horovod_tpu.serving.engine import _decode_body, _prefill_body
    scfg = tfm.TransformerConfig(
        vocab_size=512, d_model=128, n_heads=8, head_dim=16,
        n_layers=2, d_ff=256, max_seq=512, dtype=jnp.float32,
        dp_axis=None, tp_axis=None, remat=False)
    sparams = jax.eval_shape(
        lambda: tfm.init_params(scfg, jax.random.PRNGKey(0)))
    slots, page, n_max_pages, spec_k, chunk = 8, 32, 8, 3, 64
    kv = jax.ShapeDtypeStruct(
        (scfg.n_layers, slots * n_max_pages + 1, page, scfg.n_heads,
         scfg.head_dim), jnp.float32)
    serve_manifest = fusion.expected_manifest([], 0)
    i32 = jnp.int32
    serve_steps = {
        "serve_decode": (
            jax.jit(functools.partial(_decode_body, scfg),
                    donate_argnums=(1, 2)),
            (sparams, kv, kv,
             jax.ShapeDtypeStruct((slots, n_max_pages), i32),
             jax.ShapeDtypeStruct((slots,), i32),
             jax.ShapeDtypeStruct((slots,), i32))),
        "serve_prefill": (
            jax.jit(functools.partial(_prefill_body, scfg),
                    donate_argnums=(1, 2)),
            (sparams, kv, kv,
             jax.ShapeDtypeStruct((n_max_pages,), i32),
             jax.ShapeDtypeStruct((), i32),
             jax.ShapeDtypeStruct((), i32),
             jax.ShapeDtypeStruct((chunk,), i32))),
        # the decode body at batch slots*(K+1): the speculative verify
        # executable (HVD502 budget identical — speculation must not
        # smuggle in a gather either)
        "serve_spec_verify": (
            jax.jit(functools.partial(_decode_body, scfg),
                    donate_argnums=(1, 2)),
            (sparams, kv, kv,
             jax.ShapeDtypeStruct(
                 (slots * (spec_k + 1), n_max_pages), i32),
             jax.ShapeDtypeStruct((slots * (spec_k + 1),), i32),
             jax.ShapeDtypeStruct((slots * (spec_k + 1),), i32))),
    }
    for wname, (sfn, sargs) in serve_steps.items():
        fs, report = verify_report(
            sfn, sargs, expected=serve_manifest, name=wname.replace(
                "_", "-"), tag=f"verify-report-{wname}")
        findings += fs
        out["workloads"][wname] = report

    # ---- baseline + artifact --------------------------------------------
    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        ".hvdlint-baseline.json")
    baseline = {}
    if os.path.exists(baseline_path):
        baseline = load_baseline(baseline_path)
    new, baselined = split_new(findings, baseline)
    out["findings"] = [f.to_dict() for f in findings]
    out["new_findings"] = len(new)
    out["baselined_findings"] = len(baselined)

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "VERIFY.json")
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)     # atomic: no torn artifact

    for f in new:
        print(f.render(), file=sys.stderr)
    print(json.dumps({
        "metric": "verified_step_findings",
        "value": len(new),
        "unit": "non-baselined findings (HVD5xx)",
        "workloads": {k: {"collectives": len(v["collectives"]),
                          "fingerprint": v["fingerprint"]}
                      for k, v in out["workloads"].items()
                      if "collectives" in v},
        "wire_gate_failures": out.get("wire_gate_failures", []),
        "tier_gate_failures": out.get("tier_gate_failures", []),
        "detail": "VERIFY.json"}))
    return 1 if (new or out.get("wire_gate_failures")
                 or out.get("tier_gate_failures")) else 0


def cost_report_main() -> int:
    """``bench.py --cost-report``: run the resource tier (hvd.cost_report,
    HVD7xx — docs/analysis.md) over the builtin step functions on the
    hardware-free 8-device virtual CPU mesh and commit COST.json: per
    fusion HBM bytes read/written, flops, logical-vs-padded tile bytes,
    and a buffer-liveness peak-memory accounting per workload — plus the
    two headline static reproductions:

    - the ResNet-50 step at the PERF.md r2 shape (256/device, bf16,
      unfolded BN) must statically reproduce the BN wall: HVD703 fires
      on the BN chains and the projected BN-phase traffic lands within
      25% of the r2 measured attribution (69.5 ms of the 98.5 ms step);
    - a 2B-param Adam transformer gets its per-device OOM verdict
      (HVD702, with the params/optimizer/activations/buffers breakdown)
      and its replicated-optimizer-state finding (HVD704) on the 8-dev
      mesh before any chip time is spent.

    Every workload carries an expected-findings set; an unexpected OR
    missing code fails the run (exit 1) — the CI ``hvdcost`` job's
    contract, mirroring hvdverify."""
    if os.environ.get("JAX_PLATFORMS", "").lower() in ("", "cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools

    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.eager import shard_map
    from horovod_tpu.models import ResNet50
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.trainer import (
        TrainState, jit_step, make_transformer_train_step)
    from horovod_tpu.serving.engine import _decode_body

    here = os.path.dirname(os.path.abspath(__file__))
    rates = None
    try:
        with open(os.path.join(here, "SCALING.json")) as f:
            cm = json.load(f).get("cost_model_rates", {})
        rates = {k: float(cm[k])
                 for k in ("hbm_gb_s", "matmul_flop_s", "ici_gb_s")
                 if k in cm} or None
    except (OSError, ValueError):
        pass

    devs = np.array(jax.devices())
    out = {"n_devices": int(devs.size),
           "platform": jax.devices()[0].platform,
           "rates": rates, "workloads": {}}
    gate_errors = []

    def run(wname, step, args, *, expected, gates=(), **kw):
        fs, report = hvd.cost_report(step, args, name=wname, **kw)
        got = sorted({f.code for f in fs})
        report["expected_findings"] = sorted(expected)
        if got != sorted(expected):
            gate_errors.append(
                f"{wname}: findings {got} != expected {sorted(expected)}")
        for label, ok in gates:
            if not ok(report):
                gate_errors.append(f"{wname}: {label}")
        out["workloads"][wname] = report
        return report

    # ---- flagship transformer DP step (trainer-built): clean ------------
    mesh = Mesh(devs.reshape(devs.size), ("dp",))
    cfg = tfm.TransformerConfig(
        vocab_size=2048, d_model=256, n_heads=4, head_dim=64, n_layers=4,
        d_ff=1024, max_seq=256, dtype=jnp.bfloat16, dp_axis="dp")
    optimizer = optax.sgd(0.01, momentum=0.9)
    _, train_step = make_transformer_train_step(cfg, optimizer, mesh)
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    state = TrainState(jax.ShapeDtypeStruct((), jnp.int32), params,
                       jax.eval_shape(lambda: optimizer.init(params)))
    toks = jax.ShapeDtypeStruct((2 * devs.size, 256), jnp.int32)
    run("flagship-transformer-dp", train_step, (state, toks, toks),
        mesh=mesh, compute_dtype="bf16", data_axes=("dp",), rates=rates,
        expected=set(), tag="cost-report-transformer")

    # ---- ResNet-50 DP at the r2 profile shape: the static BN wall -------
    # 256/device, bf16, UNFOLDED BN — the exact config PERF.md r2
    # profiled on chip (98.5 ms step, 69.5 ms of it the BN-phase
    # convert/multiply chain). The model must rediscover that wall from
    # the HLO alone: HVD703 on the BN chains, projected BN-phase
    # traffic within 25% of the measured attribution, and HVD705 quiet
    # against the round-5 step time (PERF.md r5, measured 2026-07 on an
    # installation that no longer exists; no chip record since).
    mesh_r = Mesh(devs.reshape(devs.size), ("hvd",))
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     folded_bn=False)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3), jnp.bfloat16)))
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   op=hvd.Average, axis="hvd")

    def shard_step(state, x, y):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, upd["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_stats = jax.tree.map(lambda s: lax.pmean(s, "hvd"), new_stats)
        return (params, new_stats, opt_state), lax.pmean(loss, "hvd")

    rstep = jit_step(shard_map(shard_step, mesh_r,
                               in_specs=(P(), P("hvd"), P("hvd")),
                               out_specs=(P(), P())))
    rstate = (variables["params"], variables.get("batch_stats", {}),
              jax.eval_shape(lambda: opt.init(variables["params"])))
    bsz = 256 * devs.size
    rx = jax.ShapeDtypeStruct((bsz, 224, 224, 3), jnp.bfloat16)
    ry = jax.ShapeDtypeStruct((bsz,), jnp.int32)

    def categorize_tuple_state(label):
        # state is the (params, batch_stats, opt_state) tuple at arg 0
        if label.startswith("[0][2]"):
            return "opt_state"
        if label.startswith("[0]"):
            return "params"
        return "other"

    bn_measured_ms = 69.5          # PERF.md r2: convert_reduce x100
    #                                (47.0 ms) + multiply_add x154 (22.5)
    run("resnet50-dp", rstep, (rstate, rx, ry), mesh=mesh_r,
        compute_dtype="bf16", data_axes=("hvd",),
        categorize=categorize_tuple_state, rates=rates,
        measured_ms=101.6,
        measured_source="PERF.md r5 resnet50: 2519.41 img/s @ 256/chip "
                        "(2026-07; no chip record since)",
        expected={"HVD701", "HVD703"}, tag="cost-report-resnet50",
        gates=(
            ("projected BN-phase traffic outside 25% of the PERF.md r2 "
             "measured 69.5 ms attribution",
             lambda r: abs(r["bn_phase"]["ms"] / bn_measured_ms - 1.0)
             <= 0.25),
            ("HVD703 did not land on the BN activation chains",
             lambda r: any(int(s["reads"]) >= 3
                           for s in r["restreamed"])),
        ))

    # ---- 2B-param Adam transformer: the pre-chip OOM verdict ------------
    big = tfm.TransformerConfig(
        vocab_size=50304, d_model=4096, n_heads=32, head_dim=128,
        n_layers=8, d_ff=16384, max_seq=512, dtype=jnp.bfloat16,
        dp_axis="dp")
    bopt = optax.adam(1e-3)
    _, big_step = make_transformer_train_step(big, bopt, mesh)
    bparams = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    bstate = TrainState(jax.ShapeDtypeStruct((), jnp.int32), bparams,
                        jax.eval_shape(lambda: bopt.init(bparams)))
    btoks = jax.ShapeDtypeStruct((devs.size, 512), jnp.int32)
    run("transformer-2b-dp-adam", big_step, (bstate, btoks, btoks),
        mesh=mesh, compute_dtype="bf16", data_axes=("dp",), rates=rates,
        expected={"HVD701", "HVD702", "HVD704"},
        tag="cost-report-transformer-2b",
        gates=(
            ("HVD702 accounting breakdown incomplete",
             lambda r: all(r["accounting"][k] > 0 for k in
                           ("params_bytes", "opt_state_bytes",
                            "transient_peak_bytes", "peak_bytes"))),
            ("replicated Adam moments not dominating the verdict",
             lambda r: r["accounting"]["opt_state_bytes"]
             >= 2 * r["accounting"]["params_bytes"]),
        ))

    # ---- serve decode step (the engine's continuous-batching body) ------
    scfg = tfm.TransformerConfig(
        vocab_size=512, d_model=128, n_heads=8, head_dim=16,
        n_layers=2, d_ff=256, max_seq=512, dtype=jnp.float32,
        dp_axis=None, tp_axis=None, remat=False)
    sparams = jax.eval_shape(
        lambda: tfm.init_params(scfg, jax.random.PRNGKey(0)))
    slots, page, n_max_pages = 8, 32, 8
    kv = jax.ShapeDtypeStruct(
        (scfg.n_layers, slots * n_max_pages + 1, page, scfg.n_heads,
         scfg.head_dim), jnp.float32)
    decode = jax.jit(functools.partial(_decode_body, scfg),
                     donate_argnums=(1, 2))
    run("serve-decode", decode,
        (sparams, kv, kv,
         jax.ShapeDtypeStruct((slots, n_max_pages), jnp.int32),
         jax.ShapeDtypeStruct((slots,), jnp.int32),
         jax.ShapeDtypeStruct((slots,), jnp.int32)),
        compute_dtype="f32", rates=rates, expected=set(),
        tag="cost-report-serve-decode")

    # ---- artifact -------------------------------------------------------
    out["gate_failures"] = gate_errors
    out["remeasure_commands"] = [
        "hvdrun -np 8 -- python bench.py resnet50"
        "   # remeasure the BN wall step time (PERF.md r2 / BENCH rows)",
        "python bench.py --collectives"
        "   # re-derive the hbm/ici rates for SCALING.json "
        "cost_model_rates",
        "JAX_PLATFORMS=tpu python bench.py --cost-report"
        "   # re-verdict the HVD7xx model on real TPU HLO (no f32 "
        "legalization correction, native fusion granularity)",
    ]
    path = os.path.join(here, "COST.json")
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)     # atomic: no torn artifact

    for msg in gate_errors:
        print(f"hvdcost gate: {msg}", file=sys.stderr)
    resnet = out["workloads"]["resnet50-dp"]
    print(json.dumps({
        "metric": "cost_report_gate_failures",
        "value": len(gate_errors),
        "unit": "failed gates + unexpected findings (HVD7xx)",
        "bn_phase_ms": resnet["bn_phase"]["ms"],
        "bn_measured_ms": bn_measured_ms,
        "resnet_model_vs_measured": (resnet.get("measured") or {}).get(
            "ratio"),
        "oom_verdict_peak_gib": round(
            out["workloads"]["transformer-2b-dp-adam"]["accounting"]
            ["peak_bytes"] / 2 ** 30, 2),
        "detail": "COST.json"}))
    return 1 if gate_errors else 0


def compat_report_main() -> int:
    """``bench.py --compat-report``: run the handoff-certification tier
    (hvd.compat_report, HVD8xx — docs/analysis.md) over real committed
    artifacts on the hardware-free virtual CPU mesh and commit
    COMPAT.json:

    - the flagship handoff — a transformer TrainState committed at two
      generations through the resilience subsystem's own writer, with a
      warm artifact-store entry — must certify ``compatible`` with ALL
      FIVE rules evaluated (no skipped axis) and the optimizer
      residuals recorded as known-droppable, never as silent drops;
    - three seeded defects (a snapshot from a 2x-wider model, a
      committed resize plan retargeting a world the serving mesh does
      not have, a store entry whose env fingerprint went stale) must
      each earn EXACTLY their rule: HVD801, HVD802, HVD803.

    Every workload carries an expected-findings set; an unexpected OR
    missing code fails the run (exit 1) — the CI ``hvdcompat`` job's
    contract, mirroring hvdcost. ``--regression-report`` reads the
    committed artifact back as the ``compat_certified`` axis."""
    if os.environ.get("JAX_PLATFORMS", "").lower() in ("", "cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import struct
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.elastic.resize import ResizePlan, commit_plan
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.trainer import TrainState
    from horovod_tpu.resilience.async_checkpoint import AsyncCheckpointer
    from horovod_tpu.store.artifact_store import MAGIC, ArtifactStore

    here = os.path.dirname(os.path.abspath(__file__))
    session = tempfile.mkdtemp(prefix="hvdcompat-report-")
    out = {"n_devices": int(jax.device_count()),
           "platform": jax.devices()[0].platform, "workloads": {}}
    gate_errors = []

    def snapshot(tree, steps, name):
        d = os.path.join(session, name)
        with AsyncCheckpointer(d, interval=0, fmt="pickle",
                               max_to_keep=8) as ck:
            for s in steps:
                ck.save(s, tree, sync=True)
        return d

    def warm_store(name):
        root = os.path.join(session, name)
        store = ArtifactStore(root)
        store.publish_blob(store.key("serve", engine=name), {"slots": 8})
        return root

    def stale_env(root):
        # the seeded HVD803 defect: entry headers rewritten in place to
        # an env fingerprint no live process will ever present (payload
        # and digest untouched — only the version pin is wrong)
        for fname in os.listdir(root):
            if not fname.endswith(".hvdx"):
                continue
            path = os.path.join(root, fname)
            with open(path, "rb") as f:
                raw = f.read()
            (hlen,) = struct.unpack(
                ">I", raw[len(MAGIC):len(MAGIC) + 4])
            header = json.loads(
                raw[len(MAGIC) + 4:len(MAGIC) + 4 + hlen])
            payload = raw[len(MAGIC) + 4 + hlen:]
            header.setdefault("env", {})["jax"] = "0.0.0-stale"
            hdr = json.dumps(header, sort_keys=True).encode()
            with open(path, "wb") as f:
                f.write(MAGIC + struct.pack(">I", len(hdr)) + hdr
                        + payload)

    def run(wname, snapshot_dir, consumer, *, expected, gates=(), **kw):
        fs, report = hvd.compat_report(snapshot_dir, consumer,
                                       name=wname, **kw)
        got = sorted({f.code for f in fs})
        for f in report["findings"]:
            f.pop("fingerprint", None)  # path-keyed: volatile tmpdirs
        report["expected_findings"] = sorted(expected)
        if got != sorted(expected):
            gate_errors.append(
                f"{wname}: findings {got} != expected {sorted(expected)}")
        for label, ok in gates:
            if not ok(report):
                gate_errors.append(f"{wname}: {label}")
        out["workloads"][wname] = report
        return report

    # ---- flagship train->serve handoff: must certify ---------------------
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, head_dim=16, n_layers=2,
        d_ff=128, max_seq=64, dtype=jnp.float32, dp_axis=None,
        tp_axis=None, remat=False)
    optimizer = optax.sgd(0.01, momentum=0.9)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(jnp.zeros((), jnp.int32), params,
                       optimizer.init(params))
    run("train-serve-handoff",
        snapshot(state, steps=(100, 200), name="handoff-ckpt"), cfg,
        store_dir=warm_store("handoff-store"),
        tag="compat-report-handoff", expected=set(),
        gates=(
            ("flagship handoff not certified compatible",
             lambda r: r["verdict"] == "compatible"),
            ("a rule was skipped on the flagship handoff: all five "
             "must be evaluated (store-backed, two generations)",
             lambda r: all(v == "evaluated"
                           for v in r["rules"].values())),
            ("optimizer residuals not recorded as known-droppable",
             lambda r: any("opt_state" in k for k in r["dropped"])),
            ("previous generation not rollback-certified",
             lambda r: r["generations"]["rollback_checked"] == [100]),
        ))

    # ---- wrong-geometry snapshot: the HVD801 verdict ---------------------
    wide = tfm.TransformerConfig(
        vocab_size=256, d_model=128, n_heads=4, head_dim=32, n_layers=2,
        d_ff=256, max_seq=64, dtype=jnp.float32, dp_axis=None,
        tp_axis=None, remat=False)
    run("wrong-geometry-snapshot",
        snapshot(tfm.init_params(wide, jax.random.PRNGKey(0)),
                 steps=(100,), name="geometry-ckpt"), cfg,
        tag="compat-report-geometry", expected={"HVD801"},
        gates=(
            ("HVD801 must name the leaf and both geometries",
             lambda r: any("different model geometry" in f["message"]
                           for f in r["findings"])),
        ))

    # ---- mesh-mismatched resize plan: the HVD802 verdict -----------------
    mesh_dir = snapshot(params, steps=(100,), name="mesh-ckpt")
    commit_plan(mesh_dir, ResizePlan(step=100, old_world=1, new_world=4,
                                     direction="grow"))
    run("mesh-mismatched-resize-plan", mesh_dir, cfg,
        tag="compat-report-mesh", expected={"HVD802"},
        gates=(
            ("HVD802 must point at the documented reshard path",
             lambda r: any("not one device_put" in f["message"]
                           for f in r["findings"])),
        ))

    # ---- stale store fingerprint: the HVD803 verdict ---------------------
    stale_root = warm_store("stale-store")
    stale_env(stale_root)
    run("stale-store-fingerprint",
        snapshot(params, steps=(100,), name="stale-ckpt"), cfg,
        store_dir=stale_root, tag="compat-report-stale-store",
        expected={"HVD803"},
        gates=(
            ("HVD803 must name the recompile risk and the drifted env "
             "field",
             lambda r: any("recompile" in f["message"]
                           and "0.0.0-stale" in f["message"]
                           for f in r["findings"])),
        ))

    # ---- artifact --------------------------------------------------------
    out["gate_failures"] = gate_errors
    out["remeasure_commands"] = [
        "python bench.py --compat-report"
        "   # re-certify the seeded handoffs on the 8-dev virtual mesh",
        "JAX_PLATFORMS=tpu python bench.py --compat-report"
        "   # re-certify on real TPU (true mesh fingerprint, device_kind "
        "in the store env — the CPU run cannot prove those fields)",
        "python -m horovod_tpu.analysis --compat "
        "tests/data/compatlint/targets.py:all_bad --no-baseline"
        "   # the corpus exit-code contract (must exit exactly 1)",
    ]
    # scrub the tempdir root so the committed artifact is byte-stable
    # across runs (fingerprints never depend on paths)
    blob = json.dumps(out, indent=1).replace(
        json.dumps(session)[1:-1], "<tmpdir>")
    path = os.path.join(here, "COMPAT.json")
    with open(path + ".tmp", "w") as f:
        f.write(blob)
    os.replace(path + ".tmp", path)     # atomic: no torn artifact
    shutil.rmtree(session, ignore_errors=True)

    for msg in gate_errors:
        print(f"hvdcompat gate: {msg}", file=sys.stderr)
    handoff = out["workloads"]["train-serve-handoff"]
    print(json.dumps({
        "metric": "compat_report_gate_failures",
        "value": len(gate_errors),
        "unit": "failed gates + unexpected findings (HVD8xx)",
        "handoff_verdict": handoff["verdict"],
        "handoff_rules_evaluated": sum(
            1 for v in handoff["rules"].values() if v == "evaluated"),
        "handoff_fingerprint": handoff["fingerprint"],
        "detail": "COMPAT.json"}))
    return 1 if gate_errors else 0


def trace_report_main() -> int:
    """``bench.py --trace-report``: end-to-end drive of the tracing
    subsystem (docs/tracing.md) on the hardware-free 8-device virtual CPU
    mesh, emitting TRACE.json (committed) and a Perfetto-loadable merged
    trace in the trace dir.

    What runs, for real: the span recorder across an eager
    coordinator dispatch (negotiate/fuse/dispatch + handle wait), a
    bucketed explicit-axis DistributedOptimizer ResNet-18 DP step
    (``hvd_bucket<i>`` named_scope labels in the compiled HLO), a
    ``jax.profiler`` capture window over three steps parsed by the
    stdlib-only reader into OBSERVED overlap / exposed-collective /
    per-bucket attribution (tracing/profile.py), the straggler detector
    fed with the measured step times, and the cross-controller merge
    writer. OVERLAP.json gains an ``observed`` tier next to the
    compile-schedule tier.

    Honesty note, recorded in both artifacts: on the CPU mesh the
    "device" events are the XLA CPU thunk executor's per-op executions —
    the numbers prove the PIPELINE, not TPU concurrency; the verbatim
    remeasure commands for the next chip session ride along (the
    COLLECTIVES.json pattern)."""
    # Force the 8-device virtual mesh when targeting CPU. `jax` being in
    # sys.modules is NOT the right guard (bench's own module-level
    # horovod imports pull it in unused) — the env flags apply until the
    # backend's first device use, which hasn't happened yet here. On a
    # chip host, export JAX_PLATFORMS=tpu (see remeasure_commands) and
    # this block steps aside.
    if os.environ.get("JAX_PLATFORMS", "").lower() in ("", "cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import tracing as trace
    from horovod_tpu.config import knobs
    from horovod_tpu.eager import shard_map
    from horovod_tpu.models import ResNet18
    from horovod_tpu.parallel.trainer import jit_step
    from horovod_tpu.tracing import merge as trace_merge
    from horovod_tpu.tracing import profile as trace_profile
    from horovod_tpu.tracing import straggler as trace_straggler

    # Small buckets so the scaled-down model still produces a multi-bucket
    # schedule (the per-bucket attribution needs >1 bucket to attribute).
    bucket_bytes = 4 * 1024 * 1024
    knobs.set_override("HOROVOD_GRADIENT_BUCKET_BYTES", bucket_bytes)
    trace_dir = os.path.join(os.getcwd(), ".hvdtrace")
    knobs.set_override("HOROVOD_TRACE_DIR", trace_dir)
    hvd.init()
    trace.enable()
    mesh = hvd.mesh()
    n_dev = hvd.size()

    # ---- eager coordinator drive: negotiate/fuse/dispatch + wait spans --
    hs = [hvd.allreduce_async(np.ones((n_dev, 64), np.float32),
                              name=f"trace_report_g{i}") for i in range(3)]
    for h in hs:
        hvd.synchronize(h)

    # ---- bucketed DP step (explicit-axis DistributedOptimizer) ----------
    model = ResNet18(num_classes=100, dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3), jnp.bfloat16))
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   op=hvd.Average, axis="hvd")

    def shard_step(state, x, y):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, upd["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_stats = jax.tree.map(lambda s: lax.pmean(s, "hvd"), new_stats)
        return (params, new_stats, opt_state), lax.pmean(loss, "hvd")

    step = jit_step(shard_map(shard_step, mesh,
                              in_specs=(P(), P("hvd"), P("hvd")),
                              out_specs=(P(), P())))
    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P("hvd"))
    params = jax.device_put(variables["params"], repl)
    bstats = jax.device_put(variables.get("batch_stats", {}), repl)
    opt_state = jax.device_put(opt.init(params), repl)
    state = (params, bstats, opt_state)
    rng = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(rng.rand(n_dev, 32, 32, 3),
                                   jnp.bfloat16), data_sh)
    y = jax.device_put(jnp.asarray(rng.randint(0, 100, (n_dev,)),
                                   jnp.int32), data_sh)

    # Bucket map from the OPTIMIZED HLO: instruction names (what the
    # profiler's args.hlo_op carries) -> hvd_bucket<i> labels from the
    # named_scope metadata _sync_leaves_fused emits.
    compiled_txt = step.lower(state, x, y).compile().as_text()
    bucket_map = trace_profile.bucket_map_from_hlo(compiled_txt)
    n_buckets = len(set(bucket_map.values()))

    straggler = trace_straggler.StragglerDetector(
        None, 0, 1, window=8, publish_every=2)
    profile_steps = 3
    profiler = trace_profile.StepProfiler(
        profile_steps, 1, log_dir=os.path.join(trace_dir, "profile"),
        bucket_map=bucket_map)
    n_steps = 6
    for i in range(n_steps):
        t0 = time.perf_counter()
        step_span = trace.span("train.step", cat=trace.CAT_TRAIN,
                               attrs={"step": i})
        step_span.__enter__()
        try:
            state, loss = step(state, x, y)
            jax.block_until_ready(loss)
        finally:
            step_span.__exit__(None, None, None)
        straggler.observe_step(time.perf_counter() - t0)
        profiler.on_step_end(i + 1)
    profiler.stop()
    attribution = profiler.attribution or {}
    straggler_snap = straggler.publish_and_check()

    # ---- merged Perfetto trace ------------------------------------------
    os.makedirs(trace_dir, exist_ok=True)
    merged_path = os.path.join(trace_dir, "trace_report.trace.json")
    trace_merge.merged_chrome_trace(merged_path, kv=None,
                                    process_index=0, process_count=1)
    merged = json.load(open(merged_path))

    span_counts = trace.span_counts()
    here = os.path.dirname(os.path.abspath(__file__))
    remeasure = [
        "# next TPU session (the COLLECTIVES.json pattern) — rerun on a "
        "real slice:",
        "JAX_PLATFORMS=tpu python bench.py --trace-report   # observed "
        "tier remeasured on chip, OVERLAP.json updated in place",
        "HOROVOD_TRACE=1 HOROVOD_TRACE_PROFILE=steps:3 python bench.py "
        "transformer   # flagship capture window + span export",
        "hvdrun -np 8 -- env HOROVOD_TRACE=1 python bench.py resnet50   "
        "# multi-controller: merged trace + straggler skew over the KV "
        "store",
    ]
    out = {
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "n_devices": n_dev,
        "workload": "ResNet-18 bf16 DP step, explicit-axis "
                    "DistributedOptimizer, "
                    f"HOROVOD_GRADIENT_BUCKET_BYTES={bucket_bytes}",
        "evidence_level": (
            "CPU virtual mesh: device events are XLA CPU thunk "
            "executions — proves the capture->parse->classify->attribute "
            "pipeline end to end, NOT TPU concurrency; see remeasure"),
        "steps": {"total": n_steps, "profiled": profile_steps},
        "buckets_in_hlo": n_buckets,
        "spans": {
            "total": sum(span_counts.values()),
            "by_category": span_counts,
        },
        "observed": attribution,
        "straggler": straggler_snap,
        "perfetto_trace": {
            "path": os.path.relpath(merged_path, here),
            "events": len(merged.get("traceEvents", [])),
            "hosts": merged.get("metadata", {}).get("merged_hosts"),
        },
        "remeasure_commands": remeasure,
    }
    path = os.path.join(here, "TRACE.json")
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)     # atomic: no torn artifact

    # ---- OVERLAP.json observed tier -------------------------------------
    overlap_path = os.path.join(here, "OVERLAP.json")
    if os.path.exists(overlap_path):
        # An unreadable artifact must fail loudly: silently replacing it
        # with an observed-only dict would destroy the committed
        # compile-schedule tier, which only a TPU session can regenerate.
        overlap = json.load(open(overlap_path))
    else:
        overlap = {}
    overlap["observed"] = {
        "platform": out["platform"],
        "workload": out["workload"],
        "observed_overlap_ratio": attribution.get(
            "observed_overlap_ratio"),
        "exposed_collective_seconds_per_step": attribution.get(
            "exposed_collective_seconds_per_step"),
        "per_bucket": attribution.get("per_bucket"),
        "note": (
            "profile-measured tier (bench.py --trace-report, "
            "tracing/profile.py): union-interval algebra over classified "
            "device op events from a jax.profiler capture window. "
            "CPU-mesh numbers prove the pipeline; the TPU remeasure "
            "commands below produce the on-chip observed tier the "
            "compile-schedule tier above models."),
        "remeasure_commands": remeasure,
    }
    with open(overlap_path + ".tmp", "w") as f:
        json.dump(overlap, f, indent=1)
    os.replace(overlap_path + ".tmp", overlap_path)

    hvd.shutdown()
    knobs.clear_override("HOROVOD_GRADIENT_BUCKET_BYTES")
    knobs.clear_override("HOROVOD_TRACE_DIR")
    ok = (out["spans"]["total"] > 0
          and attribution.get("device_op_events", 0) > 0
          and attribution.get("collective_events", 0) > 0
          and n_buckets > 1)
    print(json.dumps({
        "metric": "trace_report",
        "observed_overlap_ratio": attribution.get(
            "observed_overlap_ratio"),
        "exposed_collective_seconds_per_step": attribution.get(
            "exposed_collective_seconds_per_step"),
        "buckets": n_buckets,
        "spans_total": out["spans"]["total"],
        "straggler_skew_seconds": straggler_snap.get("skew_seconds"),
        "detail": "TRACE.json"}))
    if not ok:
        print("bench.py --trace-report: pipeline incomplete (no spans, "
              "no classified device events, or single bucket)",
              file=sys.stderr)
        return 1
    return 0


def _overlap_workload() -> str:
    """Which training step the overlap compile / auto sweep analyzes:
    HVD_OVERLAP_WORKLOAD = resnet50 (default; the r5 evidence workload) or
    transformer (the flagship DP step, so =auto can prime the cache for
    the model the bucket knob actually matters most for). The cache key is
    per-workload (gradient shapes differ), so sweep each one you train."""
    w = os.environ.get("HVD_OVERLAP_WORKLOAD", "resnet50")
    if w not in ("resnet50", "transformer"):
        raise SystemExit(f"HVD_OVERLAP_WORKLOAD={w!r}: choose resnet50 or "
                         f"transformer")
    return w


def _overlap_tfm_cfg():
    """Flagship-config DP transformer for the overlap compile (bench.py
    transformer base, compiled at batch 4/chip)."""
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=16, head_dim=64,
        n_layers=16, d_ff=4096, max_seq=2048, scan_unroll=16,
        dtype=jnp.bfloat16, dp_axis="hvd", remat=False)


def _overlap_resnet_model():
    """The ResNet-50 overlap workload: (model, eval_shape'd variables) —
    shared between the compile and the auto-sweep cache key so the
    gradient tree both fingerprint is the same one."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import ResNet50
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, folded_bn=True)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128, 128, 3), jnp.bfloat16)))
    return model, variables


def _overlap_params(workload: str):
    """eval_shape'd parameter tree of the workload — exactly the gradient
    leaves the training-time auto resolution will fingerprint
    (Compression.none, which both this sweep and the benchmarks use; a
    dtype-changing compression produces a different key and falls back to
    the default with a warning)."""
    import jax
    if workload == "transformer":
        from horovod_tpu.models import transformer as tfm
        cfg = _overlap_tfm_cfg()
        return jax.eval_shape(
            lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    _, variables = _overlap_resnet_model()
    return variables["params"]


def _topology_n_devices(topology: str) -> int:
    """Device count implied by a 'family:AxB[xC]' topology string (8
    for 'v5e:2x4'), or 0 when the string is not in that form — the
    warm bucket-auto path needs the world size BEFORE any compile."""
    _, _, dims = topology.partition(":")
    try:
        n = 1
        for d in dims.split("x"):
            n *= int(d)
        return n if n > 0 else 0
    except ValueError:
        return 0


def _overlap_grad_signature(n_devices: int) -> str:
    """The autotune cache key the training-time 'auto' resolution will
    compute for this workload: gradient leaf (shape, dtype) fingerprint x
    world size (autotune.grad_signature) — deliberately NOT the topology
    name, which training-time resolution cannot know (same-world sweeps
    over different ring geometries share a key; bucket_cache_store warns
    on conflicting overwrites)."""
    import jax
    from horovod_tpu.autotune import grad_signature
    leaves = [(l.shape, l.dtype)
              for l in jax.tree.leaves(_overlap_params(_overlap_workload()))]
    return grad_signature(leaves, n_devices)


def _overlap_compile(topology: str, bucket_bytes: int,
                     compression: str = "none"):
    """AOT-compile the selected workload's explicit-axis DP step (the
    path whose gradient sync buckets — parallel/distributed.
    _sync_leaves_fused) for a multi-chip TPU topology (no chips needed —
    the real TPU compiler schedules it) and return
    (def-use graph, module_is_scheduled, n_devices). ``compression``
    sets the HOROVOD_GRADIENT_COMPRESSION wire tier for the compile, so
    the schedule's all-reduce payloads reflect the wire dtype."""
    import jax
    import jax.numpy as jnp
    import optax
    import jax.tree_util as jtu
    from jax.experimental import topologies
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import lax

    import horovod_tpu as hvd
    from horovod_tpu.config import knobs
    from horovod_tpu.eager import shard_map

    workload = _overlap_workload()
    knobs.set_override("HOROVOD_GRADIENT_BUCKET_BYTES", int(bucket_bytes))
    if compression != "none":
        knobs.set_override("HOROVOD_GRADIENT_COMPRESSION", str(compression))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=topology)
        devs = np.array(topo.devices)
        mesh = Mesh(devs.reshape(devs.size), ("hvd",))
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), op=hvd.Average, axis="hvd")

        if workload == "transformer":
            from horovod_tpu.models import transformer as tfm
            cfg = _overlap_tfm_cfg()
            params = _overlap_params(workload)

            def shard_step(params, opt_state, tokens, labels):
                loss, grads = jax.value_and_grad(
                    lambda p: tfm.loss_fn(cfg, p, tokens, labels))(params)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return params, opt_state, lax.pmean(loss, "hvd")

            fn = jax.jit(shard_map(
                shard_step, mesh=mesh,
                in_specs=(P(), P(), P("hvd"), P("hvd")),
                out_specs=(P(), P(), P())))
            B = 4 * devs.size
            opt_state = jax.eval_shape(lambda: opt.init(params))
            args = (params, opt_state,
                    jax.ShapeDtypeStruct((B, 2048), jnp.int32),
                    jax.ShapeDtypeStruct((B, 2048), jnp.int32))
        else:
            model, variables = _overlap_resnet_model()

            def shard_step(state, x, y):
                params, batch_stats, opt_state = state

                def loss_fn(p):
                    logits, upd = model.apply(
                        {"params": p, "batch_stats": batch_stats}, x,
                        train=True, mutable=["batch_stats"])
                    loss = optax.softmax_cross_entropy_with_integer_labels(
                        logits, y).mean()
                    return loss, upd["batch_stats"]

                (loss, new_stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                new_stats = jax.tree.map(lambda s: lax.pmean(s, "hvd"),
                                         new_stats)
                return (params, new_stats, opt_state), lax.pmean(loss, "hvd")

            fn = jax.jit(shard_map(shard_step, mesh=mesh,
                                   in_specs=(P(), P("hvd"), P("hvd")),
                                   out_specs=(P(), P())))
            params = variables["params"]
            bstats = variables.get("batch_stats", {})
            opt_state = jax.eval_shape(lambda: opt.init(params))
            B = 32 * devs.size
            args = ((params, bstats, opt_state),
                    jax.ShapeDtypeStruct((B, 128, 128, 3), jnp.bfloat16),
                    jax.ShapeDtypeStruct((B,), jnp.int32))
        args = jtu.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        txt = fn.lower(*args).compile().as_text()
    finally:
        knobs.clear_override("HOROVOD_GRADIENT_BUCKET_BYTES")
        knobs.clear_override("HOROVOD_GRADIENT_COMPRESSION")

    graph, scheduled = _parse_entry_graph(txt)
    return graph, scheduled, int(devs.size)


def _parse_entry_graph(txt: str):
    """Parse the (scheduled) entry computation into a def-use graph:
    {name: {"line", "kind", "bytes", "operands"}} where kind is
    'all-reduce' | 'conv' (heavy compute: conv fusions, and dot/matmul
    fusions for matmul-dense workloads like the transformer — same kind
    tag so every consumer treats them uniformly as hideable compute) |
    other. Variadic (combined) all-reduces sum all tuple element
    shapes."""
    entry = txt.split("ENTRY ")[-1]
    graph = {}
    for i, line in enumerate(entry.splitlines()):
        s = line.strip()
        # Result types may be tuples whose layouts contain parens
        # (f32[..]{0:T(8,128)S(1)}, ...) — find the opcode as the first
        # LOWERCASE word followed by '(' (layout tags T()/S() are
        # uppercase), with everything before it as the type.
        m = re.match(r"(%[\w.-]+) = (.*?) ([a-z][\w-]*)\((.*)$", s)
        if not m:
            continue
        name, shape, opcode, argstr = m.groups()
        nbytes = _shape_bytes(shape)
        if opcode in ("all-reduce", "all-reduce-start"):
            kind = "all-reduce"
        elif opcode in ("fusion", "custom-call") and (
                "convolution" in name or "conv_general_dilated" in s
                or "dot" in name or "dot_general" in s):
            # name or preserved op_name metadata marks the heavy-compute
            # fusions: convolutions (ResNet) and dots (transformer)
            kind = "conv"
        else:
            kind = opcode
        graph[name] = {"line": i, "kind": kind, "bytes": nbytes,
                       "operands": re.findall(r"%[\w.-]+", argstr)}
    return graph, ("is_scheduled=true" in txt)


def _hideable_convs(graph, ar_name):
    """Conv fusions NOT in the all-reduce's ancestor set — compute whose
    data does not feed this collective, i.e. compute an async schedule
    could run DURING it. A pure dataflow property: independent of where
    the (sync-semantics) scheduler happened to place the op."""
    seen, stack = set(), [ar_name]
    while stack:
        n = stack.pop()
        if n in seen:
            continue
        seen.add(n)
        stack.extend(op for op in graph.get(n, {}).get("operands", ())
                     if op in graph)
    total = [n for n, v in graph.items() if v["kind"] == "conv"]
    dependent = [n for n in total if n in seen]
    return len(total) - len(dependent), len(total)


def _overlap_config_entry(topology: str, bb: int,
                          compression: str = "none"):
    """Compile one bucket config and summarize its gradient collectives."""
    graph, scheduled, n_dev = _overlap_compile(topology, bb, compression)
    grad_ars = sorted(
        ((n, v) for n, v in graph.items()
         if v["kind"] == "all-reduce" and v["bytes"] > (1 << 20)),
        key=lambda kv: kv[1]["line"])
    rows = []
    for name, v in grad_ars:
        hideable, total = _hideable_convs(graph, name)
        rows.append({"bytes": v["bytes"], "schedule_line": v["line"],
                     "hideable_conv_fusions": hideable,
                     "conv_fusions_total": total})
    entry = {
        "gradient_all_reduces": len(rows),
        "grad_ars": rows,
        "hideable_conv_fraction_weighted": round(
            sum(r["bytes"] * r["hideable_conv_fusions"]
                / max(r["conv_fusions_total"], 1) for r in rows)
            / max(sum(r["bytes"] for r in rows), 1), 4),
        "module_is_scheduled": scheduled,
    }
    return entry, rows, n_dev


def _dcn_tier_ab_main(n_slices: int) -> int:
    """``HOROVOD_DCN_VIRTUAL_SLICES=k python bench.py --overlap-report``:
    the hardware-free flat-vs-two-level A/B for the DCN collective tier
    (ROADMAP item 3 deliverable; docs/hierarchical.md).

    What runs, for real, on the 8-device virtual CPU mesh split into k
    contiguous virtual slices: the explicit-axis bucketed ResNet-18 DP
    step is COMPILED under HOROVOD_DCN_SCHEDULE=flat and =two_level and
    the optimized HLO's collective structure compared (the two-level
    schedule must replace each bucket's world all-reduce with
    reduce-scatter + cross-slice all-reduce + all-gather); one step of
    each EXECUTES and the parameters must agree to 1e-5 (numerical
    equivalence, the same property tests/test_dcn_tier.py pins per op x
    dtype x shard shape). Each bucket schedule is then scored with the
    SEPARATE ICI-vs-DCN latency/bandwidth terms (SCALING.json
    dcn_tier_model; autotune.score_bucket_schedule) for flat, two-level,
    and two-level + fp8-compressed-cross-tier. Honesty note, recorded in
    the artifact: the times are MODEL-scored — CPU devices share one
    host, so no wall-clock here measures DCN; the verbatim remeasure
    commands for a real multi-slice session ride along
    (COLLECTIVES.json pattern)."""
    if os.environ.get("JAX_PLATFORMS", "").lower() in ("", "cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import autotune
    from horovod_tpu.analysis import rules_ir
    from horovod_tpu.config import knobs
    from horovod_tpu.eager import shard_map
    from horovod_tpu.models import ResNet18
    from horovod_tpu.ops.fusion import _plan_buckets_by_bytes
    from horovod_tpu.parallel.trainer import jit_step
    from horovod_tpu.runtime.topology import DCN_AXIS

    devs = np.array(jax.devices())
    n = int(devs.size)
    if n % n_slices:
        print(f"--overlap-report: {n} devices do not split into "
              f"{n_slices} virtual slices", file=sys.stderr)
        return 2
    n_ici = n // n_slices
    mesh = Mesh(devs.reshape(n_slices, n_ici), (DCN_AXIS, "hvd"))
    axes = (DCN_AXIS, "hvd")
    bucket_bytes = 4 * 1024 * 1024
    knobs.set_override("HOROVOD_GRADIENT_BUCKET_BYTES", bucket_bytes)

    model = ResNet18(num_classes=100, dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3), jnp.bfloat16))
    # host copies: device_put aliases already-placed arrays, and the
    # donated step would otherwise delete the source tree between the
    # flat and two_level runs
    variables = jax.tree.map(np.asarray, variables)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   op=hvd.Average, axis=axes)

    def shard_step(state, x, y):
        params, batch_stats, opt_state = state

        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, upd["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_stats = jax.tree.map(lambda s: lax.pmean(s, axes), new_stats)
        return (params, new_stats, opt_state), lax.pmean(loss, axes)

    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P(axes))
    rng = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(rng.rand(n, 32, 32, 3),
                                   jnp.bfloat16), data_sh)
    y = jax.device_put(jnp.asarray(rng.randint(0, 100, (n,)),
                                   jnp.int32), data_sh)

    configs = {}
    results = {}
    for schedule in ("flat", "two_level"):
        # fresh jit + fresh state per schedule: the knob is read at
        # TRACE time (a shared jit would reuse the first schedule's
        # program) and jit_step donates the state argument
        knobs.set_override("HOROVOD_DCN_SCHEDULE", schedule)
        try:
            step = jit_step(shard_map(shard_step, mesh,
                                      in_specs=(P(), P(axes), P(axes)),
                                      out_specs=(P(), P())))
            params = jax.device_put(variables["params"], repl)
            bstats = jax.device_put(variables.get("batch_stats", {}),
                                    repl)
            opt_state = jax.device_put(opt.init(params), repl)
            state = (params, bstats, opt_state)
            compiled = step.lower(state, x, y).compile()
            entries = rules_ir.hlo_collectives(compiled.as_text())
            (out_state, _) = step(state, x, y)
        finally:
            knobs.clear_override("HOROVOD_DCN_SCHEDULE")
        by_kind = {}
        for e in entries:
            row = by_kind.setdefault(e["kind"], {"count": 0, "bytes": 0})
            row["count"] += 1
            row["bytes"] += e["bytes"]
        configs[schedule] = {"collectives": by_kind,
                             "total_collectives": len(entries)}
        results[schedule] = jax.tree.map(np.asarray, out_state[0])
    max_delta = max(
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree.leaves(results["flat"]),
                        jax.tree.leaves(results["two_level"])))
    knobs.clear_override("HOROVOD_GRADIENT_BUCKET_BYTES")

    # Model-scored A/B with the separate ICI/DCN terms, per bucket of
    # the real schedule (hideable fractions are left 0 here — the A/B
    # compares schedules, not overlap; the TPU overlap compile owns
    # that evidence).
    sizes = [int(np.prod(np.shape(l), dtype=np.int64))
             * jnp.asarray(l).dtype.itemsize
             for l in jax.tree.leaves(variables["params"])]
    buckets = _plan_buckets_by_bytes(sizes, bucket_bytes)
    rows = [{"bytes": sum(sizes[i] for i in b)} for b in buckets]
    scores = {
        "flat": autotune.score_bucket_schedule(
            rows, n, schedule="flat", dcn_slices=n_slices),
        "two_level": autotune.score_bucket_schedule(
            rows, n, schedule="two_level", dcn_slices=n_slices),
        "two_level_compressed": autotune.score_bucket_schedule(
            rows, n, schedule="two_level_compressed",
            dcn_slices=n_slices, wire_itemsize=1),
    }
    winner = min(scores, key=lambda s: scores[s]["comm_s"])

    two = configs["two_level"]["collectives"]
    problems = []
    if max_delta > 1e-5:
        problems.append(f"flat vs two_level parameter delta {max_delta} "
                        f"exceeds 1e-5")
    for want in ("reduce-scatter", "all-gather"):
        if want not in two:
            problems.append(f"two_level compile has no {want} — the "
                            f"tier did not engage")

    out = {
        "mode": "virtual_slice_dcn_tier_ab",
        "n_devices": n,
        "virtual_slices": n_slices,
        "ici_world": n_ici,
        "workload": "ResNet-18 bf16 DP step, batch 1/chip @32px, "
                    "4 MiB buckets (virtual CPU mesh)",
        "evidence_level":
            "compiled collective structure + 1-step numerical "
            "equivalence on the virtual CPU mesh; times are "
            "MODEL-scored (SCALING.json dcn_tier_model ICI vs DCN "
            "terms), NOT measured — no DCN exists on one host",
        "configs": configs,
        "max_param_delta_flat_vs_two_level": max_delta,
        "model_scores": {k: {"comm_s": v["comm_s"],
                             "collectives": v["collectives"]}
                         for k, v in scores.items()},
        "model_winner": winner,
        "latency_model": autotune.score_dcn_schedules(
            sum(sizes), n_ici, n_slices,
            wire_itemsize=1)["latency_model"],
        "remeasure_commands": [
            f"HOROVOD_DCN_VIRTUAL_SLICES={n_slices} python bench.py "
            f"--overlap-report",
            "HOROVOD_DCN_MESH=<slices,chips_per_slice> "
            "HOROVOD_DCN_SCHEDULE=flat python bench.py transformer",
            "HOROVOD_DCN_MESH=<slices,chips_per_slice> "
            "HOROVOD_DCN_SCHEDULE=two_level python bench.py transformer",
            "HOROVOD_DCN_MESH=<slices,chips_per_slice> "
            "HOROVOD_DCN_SCHEDULE=two_level "
            "HOROVOD_GRADIENT_COMPRESSION=fp8_e4m3 "
            "python bench.py transformer",
        ],
    }
    here = os.environ.get("HVD_OVERLAP_DIR") \
        or os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "OVERLAP.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    merged["dcn_tier_ab"] = out
    with open(path + ".tmp", "w") as f:
        json.dump(merged, f, indent=1)
    os.replace(path + ".tmp", path)     # atomic: no torn artifact
    print(json.dumps({
        "metric": "dcn_tier_model_comm_s",
        "value": scores["two_level"]["comm_s"],
        "unit": "model seconds/step (two_level)",
        "vs_flat": scores["flat"]["comm_s"],
        "vs_compressed": scores["two_level_compressed"]["comm_s"],
        "model_winner": winner,
        "max_param_delta": max_delta,
        "two_level_collectives": two,
        "detail": "OVERLAP.json dcn_tier_ab"}))
    for p in problems:
        print(f"dcn tier A/B: {p}", file=sys.stderr)
    hvd.shutdown()
    return 1 if problems else 0


def overlap_report_main() -> int:
    """Writes OVERLAP.json: where the gradient all-reduces sit in the REAL
    TPU compiler's schedule relative to backward convolutions, per bucket
    config. The bucketed schedule's property — each bucket's collective
    scheduled as its gradients become ready, backward conv fusions
    interleaved between collectives — is the compiler-visible form of the
    reference's comm/compute overlap (operations.cc:383-402, per-parameter
    hooks torch/optimizer.py:167-174). Evidence level: compile-schedule
    dataflow, NOT observed concurrency (see PERF.md r5 'Limits, honestly').

    With HOROVOD_GRADIENT_BUCKET_BYTES=auto this is also the knob's AOT
    tuner (the parameter-manager analogue, parameter_manager.cc:44-61):
    every candidate in autotune.BUCKET_CANDIDATES_MIB is compiled, scored
    by exposed-communication time under the SCALING.json ring latency
    model, recorded in OVERLAP.json's auto_sweep section, and the winner
    is cached per (gradient shapes, world size — the fields training-time
    resolution can recompute) so 'auto' resolves
    to it (autotune.resolve_bucket_bytes). HVD_OVERLAP_WORKLOAD selects
    the analyzed step (resnet50 | transformer) — sweep each workload you
    train with auto, the cache keys are per-model."""
    topology = os.environ.get("HVD_OVERLAP_TOPOLOGY", "v5e:2x4")
    from horovod_tpu import autotune
    from horovod_tpu.config import knobs
    # Virtual-slice mode (HOROVOD_DCN_VIRTUAL_SLICES >= 2): the
    # hardware-free DCN-tier A/B — compiled collective structure +
    # numerical equivalence + ICI-vs-DCN model scores on the virtual CPU
    # mesh (the tier-smoke CI step). The TPU AOT overlap path below
    # needs the real compiler and stays single-slice.
    n_virtual = int(knobs.get("HOROVOD_DCN_VIRTUAL_SLICES") or 0)
    if n_virtual > 1:
        return _dcn_tier_ab_main(n_virtual)
    raw = knobs.get("HOROVOD_GRADIENT_BUCKET_BYTES")
    auto = raw == "auto"
    if not auto and int(raw) <= 0:
        print("bench.py --overlap-report: HOROVOD_GRADIENT_BUCKET_BYTES "
              "is 0 (bucketing disabled) — nothing to compare",
              file=sys.stderr)
        return 2
    workload = _overlap_workload()
    out = {"topology": topology, "workload": {
               "resnet50":
                   "ResNet-50 bf16 DP fused-mode step, batch 32/chip "
                   "@128px",
               "transformer":
                   "268M TransformerLM bf16 DP step (flagship bench "
                   "config), batch 4/chip @S=2048",
           }[workload],
           "evidence_level":
               "compile-schedule position + dependence graph from the AOT "
               "TPU compile — NOT observed concurrent execution (the "
               "backend lowers sync all-reduce HLO; actual overlap happens "
               "in its low-level scheduler)",
           "configs": {}}
    sweep_rows, n_dev, warm, key = {}, None, None, None
    if auto:
        # Warm bucket-auto path (hvdstore): a previous sweep for this
        # (grad signature, world, workload) persisted its full evidence
        # — candidate scores, winner, wire-tier A/B — through the
        # compiled-artifact store, so EVERY candidate compile is
        # skipped (hvd_bucket_auto_warm_hits_total counts the hit). The
        # winner's training executable is served by the step tier of
        # the same store at train time.
        n_guess = _topology_n_devices(topology)
        if n_guess:
            warm = autotune.load_auto_sweep(
                _overlap_grad_signature(n_guess), workload)
            if warm is not None \
                    and int(warm.get("n_devices") or 0) != n_guess:
                warm = None             # stale world: sweep for real
        if warm is not None:
            n_dev = int(warm["n_devices"])
            out["configs"].update(warm["configs"])
            sweep = dict(warm["sweep"])
            sweep["warm_from_store"] = True
        else:
            entry, _, n_dev = _overlap_config_entry(topology, 0)
            out["configs"]["0"] = entry
            for mib in autotune.BUCKET_CANDIDATES_MIB:
                bb = int(mib) << 20
                entry, rows, n_dev = _overlap_config_entry(topology, bb)
                out["configs"][str(bb)] = entry
                sweep_rows[bb] = rows
            sweep = autotune.auto_bucket_search(
                lambda bb: sweep_rows[bb], n_dev,
                candidates=autotune.BUCKET_CANDIDATES_MIB)
        key = _overlap_grad_signature(n_dev)
        autotune.bucket_cache_store(key, sweep["winner_bucket_bytes"])
        sweep["cache_key"] = key
        sweep["cache_path"] = autotune._bucket_cache_path()
        out["auto_sweep"] = sweep
        default_bb = int(sweep["winner_bucket_bytes"])
    else:
        default_bb = int(raw)
        for bb in (0, default_bb):
            entry, _, n_dev = _overlap_config_entry(topology, bb)
            out["configs"][str(bb)] = entry

    # Wire-compression sweep at the chosen bucket size: each tier is a
    # real AOT compile (the schedule's all-reduce payloads carry the
    # wire dtype), scored by the same ring latency model — smaller wire
    # payloads shrink ring time, the hideable-compute fractions are
    # re-measured from each compiled schedule. Evidence level matches
    # the bucket sweep: compile-schedule + model score, NOT a chip
    # measurement — the verbatim remeasure commands below are the next
    # TPU session's job (BENCH_TRANSFORMER.json pending pattern).
    if warm is not None and warm.get("compression_sweep"):
        out["compression_sweep"] = dict(warm["compression_sweep"],
                                        warm_from_store=True)
    else:
        comp_tiers = {}
        for tier in ("none", "bf16", "fp8_e4m3"):
            entry, rows, n_dev = _overlap_config_entry(
                topology, default_bb, tier)
            entry["model_score"] = autotune.score_bucket_schedule(rows,
                                                                  n_dev)
            comp_tiers[tier] = entry
        bench_cmd = "python bench.py" + (
            " transformer" if workload == "transformer" else "")
        out["compression_sweep"] = {
            "bucket_bytes": default_bb,
            "tiers": comp_tiers,
            "model_winner_tier": min(
                comp_tiers,
                key=lambda t:
                comp_tiers[t]["model_score"]["exposed_comm_s"]),
            "status": "model_scored_pending_chip_measurement",
            "remeasure_commands": [
                f"HVD_OVERLAP_WORKLOAD={workload} python bench.py "
                f"--overlap-report",
                f"HOROVOD_GRADIENT_COMPRESSION=bf16 {bench_cmd}",
                f"HOROVOD_GRADIENT_COMPRESSION=fp8_e4m3 {bench_cmd}",
            ],
        }
    if auto and warm is None and key is not None:
        # Cold sweep completed: persist the full evidence so the next
        # process's auto run skips every candidate compile.
        autotune.persist_auto_sweep(key, workload, {
            "n_devices": int(n_dev),
            "configs": dict(out["configs"]),
            "sweep": {k: v for k, v in sweep.items()
                      if k != "cache_path"},
            "compression_sweep": out["compression_sweep"],
        })
    here = os.environ.get("HVD_OVERLAP_DIR") \
        or os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "OVERLAP.json")
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)     # atomic: no torn artifact
    single = out["configs"]["0"]
    bucketed = out["configs"][str(default_bb)]
    summary = {
        "metric": "gradient_sync_hideable_conv_fraction",
        "value": bucketed["hideable_conv_fraction_weighted"],
        "unit": "fraction (payload-weighted)",
        "vs_baseline": single["hideable_conv_fraction_weighted"],
        "buckets": bucketed["gradient_all_reduces"],
        "detail": "OVERLAP.json"}
    if auto:
        summary["auto_winner_bucket_bytes"] = default_bb
    print(json.dumps(summary))
    return 0


def goodput_smoke_main() -> int:
    """--goodput-smoke: a short REAL train_loop run on the virtual mesh
    that exercises the whole hvdgoodput surface — phase attribution
    across input-wait/step/checkpoint, the exposed-collective and
    compile carves, a ledger record — and asserts the accountant's
    invariant: the phase breakdown sums to total wall time within 1%.
    The CI goodput-smoke job runs this, then --regression-report over
    the ledger it wrote."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.config import knobs
    from horovod_tpu.goodput import ledger as goodput_ledger
    from horovod_tpu.parallel import trainer

    hvd.init()
    mesh = hvd.mesh()
    optimizer = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Average)

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    init_fn, train_step, put_batch = trainer.data_parallel_train_step(
        loss_fn, optimizer, mesh)
    rng = np.random.RandomState(0)
    state = init_fn({"w": jnp.asarray(rng.rand(16, 1), jnp.float32),
                     "b": jnp.zeros((1,), jnp.float32)})
    n_steps = int(os.environ.get("HVD_GOODPUT_SMOKE_STEPS", "12"))

    def batches():
        for _ in range(n_steps):
            x = rng.rand(hvd.size() * 4, 16).astype(np.float32)
            y = (x.sum(axis=1, keepdims=True)).astype(np.float32)
            yield (put_batch((x, y)),)

    state, info = trainer.train_loop(train_step, state, batches())
    report = hvd.goodput_report()
    record = goodput_ledger.append_record(
        bench={"metric": "goodput_smoke_steps", "value": info["final_step"],
               "unit": "steps"})
    hvd.shutdown()

    total = report["total_seconds"]
    attributed = report["attributed_seconds"]
    closes = abs(attributed - total) <= 0.01 * max(total, 1e-9)
    summary = {
        "metric": "goodput_fraction",
        "value": report["goodput_fraction"],
        "unit": "fraction of wall time",
        "phases": report["phases"],
        "total_seconds": total,
        "attributed_seconds": attributed,
        "breakdown_closes_within_1pct": closes,
        "steps": info["final_step"],
        "ledger_path": knobs.get("HOROVOD_GOODPUT_LEDGER") or None,
        "ledger_written": record is not None,
    }
    print(json.dumps(summary))
    if not closes:
        print(f"bench.py --goodput-smoke: phase breakdown "
              f"({attributed:.6f}s) does not close against total wall "
              f"time ({total:.6f}s) within 1%", file=sys.stderr)
        return 1
    if report["phases"]["step_compute"] <= 0:
        print("bench.py --goodput-smoke: no step_compute time "
              "attributed", file=sys.stderr)
        return 1
    return 0


def _worker_env() -> dict:
    """Environment for the store/serve worker children. The parent stays
    off JAX (one process owns the chips), so the platform is the
    workers' to find — and they fail without a TPU (require_chip). Only
    a caller that asked for the CPU by name gets the eight virtual
    devices the CI gates run on."""
    env = dict(os.environ)
    if cpu_by_name():
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    return env


def store_worker_main() -> int:
    """--store-worker (internal child of --store-report): one short
    incarnation of a store-enabled training process. Measures
    time-to-first-step from the parent's spawn stamp (HVD_T0), runs one
    eager fused allreduce (the coordinator ExecutableCache consumer) and
    a checkpointed train_loop (the step-adoption + restore consumers),
    then prints ONE JSON line with the TTFS, the goodput phase
    breakdown, the store tallies, and the executable-cache counters the
    parent's cold-vs-warm assertions read."""
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel import trainer
    from horovod_tpu.store import artifact_store as store_mod

    t0 = float(os.environ.get("HVD_T0") or time.time())
    require_chip("--store-worker")
    ctx = hvd.init()
    mesh = hvd.mesh()
    optimizer = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Average)
    rng = np.random.RandomState(0)
    # Deep enough that the XLA compile dominates the restore cost (the
    # quantity the A/B exists to measure); small enough for CI.
    D, H, LAYERS = 64, 192, int(os.environ.get("HVD_STORE_WORKER_LAYERS",
                                               "30"))

    def loss_fn(params, batch):
        x, y = batch
        h = jnp.tanh(x @ params["w_in"])
        for i in range(LAYERS):
            h = jnp.tanh(h @ params[f"w{i}"]) + h
        return jnp.mean((h @ params["w_out"] - y) ** 2)

    init0 = {"w_in": jnp.asarray(rng.rand(D, H) * 0.1, jnp.float32),
             "w_out": jnp.asarray(rng.rand(H, 1) * 0.1, jnp.float32)}
    for i in range(LAYERS):
        init0[f"w{i}"] = jnp.asarray(rng.rand(H, H) * 0.1, jnp.float32)
    init_fn, train_step, put_batch = trainer.data_parallel_train_step(
        loss_fn, optimizer, mesh)
    state = init_fn(init0)
    # Fully place the restore template: a half-placed TrainState (params
    # on the mesh, step on one device) is unusable after a templated
    # orbax restore (see checkpoint.restore_checkpoint's docstring).
    import jax as _jax
    from jax.sharding import NamedSharding as _NS, PartitionSpec as _P
    state = state._replace(
        step=_jax.device_put(state.step, _NS(mesh, _P())))
    # Consumer 1 probe: one fused eager dispatch through the
    # coordinator's ExecutableCache (same signature every incarnation).
    hvd.allreduce_async(
        jnp.arange(hvd.size() * 128, dtype=jnp.float32).reshape(
            hvd.size(), 128),
        name="store_report_probe").wait()
    first_step_at = []

    def on_step(step, state, loss):
        if not first_step_at:
            first_step_at.append(time.time())

    n_steps = int(os.environ.get("HVD_STORE_WORKER_STEPS", "4"))
    step_sleep = float(os.environ.get("HVD_STORE_WORKER_STEP_SLEEP",
                                      "0"))

    def batches():
        for _ in range(n_steps):
            if step_sleep:       # paces the loop so async checkpoint
                #                  commits land (chaos kill tests)
                time.sleep(step_sleep)
            x = rng.rand(hvd.size() * 4, D).astype(np.float32)
            y = x.sum(axis=1, keepdims=True)
            yield (put_batch((x, y)),)

    checkpointer = None
    if os.environ.get("HVD_STORE_WORKER_SYNC_CKPT"):
        # Chaos kill tests: commit EVERY step synchronously so the set
        # of committed snapshots at the kill point is deterministic
        # under any machine load (async commits would race the kill).
        from horovod_tpu.config import knobs as _knobs
        from horovod_tpu.resilience import AsyncCheckpointer

        class _SyncEveryStep(AsyncCheckpointer):
            def maybe_save(self, step, state):
                self.save(step, state, sync=True)

        checkpointer = _SyncEveryStep(_knobs.get("HOROVOD_CKPT_DIR"))
    state, info = trainer.train_loop(train_step, state, batches(),
                                     checkpointer=checkpointer,
                                     on_step=on_step)
    if checkpointer is not None:
        checkpointer.close()
    cache_snap = ctx.coordinator.cache.snapshot() \
        if ctx.coordinator is not None else {}
    goodput = hvd.goodput_report()
    summary = {
        "ttfs_s": round((first_step_at[0] - t0), 3)
        if first_step_at else None,
        "steps": info.get("final_step"),
        "restored": info.get("restored"),
        "store_step": info.get("store_step"),
        "goodput_phases": goodput["phases"],
        "store": store_mod.store_stats(),
        "cache": cache_snap,
        "final_param_digest": __import__("hashlib").sha256(
            np.ascontiguousarray(
                np.asarray(state.params["w_out"],
                           dtype=np.float32)).tobytes()).hexdigest(),
    }
    hvd.shutdown()
    print(json.dumps(summary))
    return 0


def store_report_main() -> int:
    """--store-report: the cold-vs-warm artifact-store A/B (ROADMAP
    item 5 measuring stick). Spawns --store-worker twice against ONE
    store + checkpoint directory: the cold incarnation compiles and
    publishes everything; the warm incarnation is a restart (restore +
    store adoption) and must perform ZERO executable-cache builder
    invocations, serve its train step from the store, and show a ~0
    goodput ``compile`` phase. Writes the measured time-to-first-step
    A/B to BENCH_TTFS.json (committed artifact) and exits 1 when any
    warm-path gate fails."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    workdir = tempfile.mkdtemp(prefix="hvdstore-bench-")
    env = _worker_env()
    env.update(
        HOROVOD_ARTIFACT_STORE=os.path.join(workdir, "store"),
        HOROVOD_CKPT_DIR=os.path.join(workdir, "ckpt"),
        HOROVOD_CKPT_INTERVAL="2",
        HOROVOD_GOODPUT="1",
    )

    def run(tag: str) -> dict:
        child_env = dict(env, HVD_T0=repr(time.time()))
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--store-worker"],
            env=child_env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            print(proc.stdout, file=sys.stderr)
            print(proc.stderr, file=sys.stderr)
            raise RuntimeError(
                f"--store-report: {tag} worker exited "
                f"{proc.returncode}")
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
        raise RuntimeError(f"--store-report: no JSON line from the "
                           f"{tag} worker")

    try:
        cold = run("cold")
        warm = run("warm")
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    errors = []
    if warm.get("cache", {}).get("builds") != 0:
        errors.append(
            f"warm run invoked the ExecutableCache builder "
            f"{warm.get('cache', {}).get('builds')} time(s); the store "
            f"must serve every fused program")
    if not warm.get("cache", {}).get("store_hits"):
        errors.append("warm run recorded no executable-cache store hits")
    if warm.get("store_step") != "hit":
        errors.append(f"warm train step was not served from the store "
                      f"(outcome: {warm.get('store_step')})")
    if not warm.get("restored"):
        errors.append("warm run did not restore the cold run's "
                      "checkpoint (the resume path was not exercised)")
    cold_compile = float(cold["goodput_phases"].get("compile") or 0.0)
    warm_compile = float(warm["goodput_phases"].get("compile") or 0.0)
    # ~0: a warm restart's carved compile seconds must be noise next to
    # the cold incarnation's (the phases are wall-clock measured, so an
    # absolute floor keeps slow CI machines honest).
    if warm_compile > max(0.05, 0.05 * cold_compile):
        errors.append(
            f"warm goodput compile phase is {warm_compile:.3f}s "
            f"(cold: {cold_compile:.3f}s) — expected ~0")
    artifact = {
        "metric": "time_to_first_step_seconds",
        "unit": "seconds (process spawn -> first train step complete)",
        "workload": "store-worker MLP DP step + eager fused allreduce "
                    "probe, 8-device virtual mesh",
        "cold": cold,
        "warm": warm,
        "ttfs_speedup": (round(cold["ttfs_s"] / warm["ttfs_s"], 3)
                         if cold.get("ttfs_s") and warm.get("ttfs_s")
                         else None),
        "compile_seconds_saved_warm": round(
            float((warm.get("store") or {}).get(
                "compile_seconds_saved", 0.0)), 6),
        "warm_gates": {"errors": errors},
        "remeasure_commands": [
            "python bench.py --store-report",
            "JAX_PLATFORMS=tpu python bench.py --store-report",
        ],
    }
    path = os.path.join(here, "BENCH_TTFS.json")
    with open(path + ".tmp", "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps({
        "metric": "ttfs_cold_vs_warm",
        "cold_ttfs_s": cold.get("ttfs_s"),
        "warm_ttfs_s": warm.get("ttfs_s"),
        "warm_compile_s": warm_compile,
        "cold_compile_s": cold_compile,
        "warm_builder_invocations": warm.get("cache", {}).get("builds"),
        "errors": errors,
        "artifact": path,
    }))
    if errors:
        for e in errors:
            print(f"bench.py --store-report: {e}", file=sys.stderr)
        return 1
    return 0


def serve_worker_main() -> int:
    """--serve-worker: one serving replica on the 8-device virtual CPU
    mesh. Boots the TP-sharded engine from the shared checkpoint +
    artifact store (cold publishes, warm must be compile-free), probes
    time-to-first-token, then — in the cold phase — drives the shared
    open-loop Poisson trace through the continuous-batching scheduler
    AND the static-batch baseline. Prints ONE JSON line."""
    t_spawn = float(os.environ.get("HVD_T0") or time.time())
    import numpy as np_
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.resilience import AsyncCheckpointer
    from horovod_tpu.serving import (Request, ServeEngine, ServeScheduler,
                                     load_for_serving, serving_stats)

    from horovod_tpu.config import knobs

    phase = os.environ.get("HVD_SERVE_PHASE", "cold")
    seed = int(os.environ.get("HVD_SERVE_SEED", "0"))
    n_requests = int(os.environ.get("HVD_SERVE_REQUESTS", "24"))
    rate = float(os.environ.get("HVD_SERVE_RATE", "200"))   # req/s
    ckpt_dir = knobs.get("HOROVOD_CKPT_DIR")
    if not ckpt_dir:
        print("bench.py --serve-worker: HOROVOD_CKPT_DIR must be set "
              "(the serve parent exports it)", file=sys.stderr)
        return 2

    require_chip("--serve-worker")
    hvd.init()
    mesh = Mesh(np_.array(jax.devices()), ("tp",))
    tp = int(mesh.shape["tp"])
    cfg = tfm.TransformerConfig(
        vocab_size=512, d_model=128, n_heads=max(tp, 8), head_dim=16,
        n_layers=2, d_ff=256, max_seq=512, dtype=jnp.float32,
        dp_axis=None, tp_axis="tp", remat=False)
    # Engine geometry: HOROVOD_SERVE_* knobs win when the operator set
    # them (the TPU remeasure commands in BENCH_SERVE.json rely on it);
    # otherwise CPU-bench-sized defaults keep the virtual-mesh run fast.
    def knob_or(name, bench_default):
        return knobs.get(name) if name in os.environ else bench_default
    geometry = dict(
        slots=knob_or("HOROVOD_SERVE_SLOTS", 8),
        page=knob_or("HOROVOD_SERVE_PAGE", 32),
        max_seq=knob_or("HOROVOD_SERVE_MAX_SEQ", 256),
        prefill_chunk=knob_or("HOROVOD_SERVE_PREFILL_CHUNK", 64),
    )

    if phase == "cold":
        # train->serve handoff end to end: the "training" snapshot
        # (params + optimizer momentum) is committed through the
        # resilience path, then restored param-only onto the TP mesh.
        from horovod_tpu.parallel.trainer import TrainState
        params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
        state = TrainState(jnp.asarray(100, jnp.int32), params,
                           jax.tree.map(jnp.zeros_like, params))
        with AsyncCheckpointer(ckpt_dir, interval=0, fmt="pickle") as ck:
            ck.save(100, state, sync=True)
    restored_step, params = load_for_serving(ckpt_dir, mesh, cfg)

    # The warm replica boots with the FULL hvdspec surface on (prefix
    # cache + truncated-layer self-draft): its builds==0 gate then
    # covers the verify/draft/COW executables the cold sweeps publish,
    # not just prefill/decode.
    spec_on = dict(prefix_cache=True, draft="truncate:1") \
        if phase == "warm" else {}
    engine = ServeEngine(cfg, params, mesh, **geometry, **spec_on)
    # time-to-first-token probe: process spawn -> one generated token
    # (restore + AOT/store boot included — the serving BENCH_TTFS).
    # time.time() on both sides: t_spawn is the parent's epoch stamp.
    probe = ServeScheduler(engine, queue_deadline=0.0)
    probe.run([Request(rid=-1,
                       prompt=np_.arange(8, dtype=np_.int32),
                       max_new_tokens=1)])
    ttfb_s = time.time() - t_spawn if os.environ.get("HVD_T0") else None

    def trace():
        # fresh generator per call: continuous and static see the
        # IDENTICAL arrival/prompt/length trace
        rng = np_.random.default_rng(seed)
        arrivals = np_.cumsum(rng.exponential(1.0 / rate, n_requests))
        return [Request(rid=i,
                        prompt=rng.integers(
                            0, cfg.vocab_size,
                            int(rng.integers(8, 48))).astype(np_.int32),
                        max_new_tokens=int(rng.integers(8, 25)),
                        arrival=float(arrivals[i]))
                for i in range(n_requests)]

    def percentiles(xs):
        if not xs:
            return {"p50": None, "p99": None}
        return {"p50": round(float(np_.percentile(xs, 50)) * 1e3, 3),
                "p99": round(float(np_.percentile(xs, 99)) * 1e3, 3)}

    def run_mode(mode):
        sched = ServeScheduler(engine, mode=mode)
        t0 = time.perf_counter()
        done = sched.run(trace())
        dt = time.perf_counter() - t0
        gen = sum(len(r.tokens) for r in done)
        st = sched.stats()
        return {
            "completed": len(done),
            "generated_tokens": gen,
            "duration_s": round(dt, 4),
            "tokens_per_s": round(gen / dt, 2),
            "ttft_ms": percentiles([r.ttft for r in done
                                    if r.ttft is not None]),
            "tpot_ms": percentiles([t for r in done for t in r.tpot]),
            "batch_occupancy": st["mean_occupancy"],
            "queue_depth_peak": st["queue_peak"],
            "decode_steps": st["decode_steps"],
        }

    out = {
        "phase": phase,
        "restored_step": restored_step,
        "builds": engine.builds,
        "store_outcomes": engine.store_outcomes,
        "ttfb_boot_s": round(ttfb_s, 4) if ttfb_s is not None else None,
        "tp": tp,
        "geometry": geometry,
    }
    if phase == "cold":
        # the traffic A/B runs in the cold replica only: the warm
        # replica exists to prove the compile-free boot
        out["continuous"] = run_mode("continuous")
        out["static"] = run_mode("static")

        # ---- hvdspec sweeps ------------------------------------------
        # Shared-system-prompt traffic: a 64-token system prefix is
        # prepended to `frac` of the requests. Identical trace per
        # fraction across cache-off/cache-on (and the spec engines), so
        # the uplift AND the bitwise-equality gate are apples-to-apples.
        system_prompt = np_.random.default_rng(seed + 1).integers(
            0, cfg.vocab_size, 64).astype(np_.int32)

        def mixed_trace(frac):
            rng = np_.random.default_rng(seed)
            arrivals = np_.cumsum(rng.exponential(1.0 / rate, n_requests))
            reqs = []
            for i in range(n_requests):
                tail = rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(8, 48))).astype(np_.int32)
                n_new = int(rng.integers(8, 25))
                prompt = (np_.concatenate([system_prompt, tail])
                          if rng.random() < frac else tail)
                reqs.append(Request(rid=i, prompt=prompt,
                                    max_new_tokens=n_new,
                                    arrival=float(arrivals[i])))
            return reqs

        def run_trace(eng, reqs):
            sched = ServeScheduler(eng, mode="continuous")
            t0 = time.perf_counter()
            done = sched.run(reqs)
            dt = time.perf_counter() - t0
            gen = sum(len(r.tokens) for r in done)
            tokens = [r.tokens for r in sorted(done, key=lambda r: r.rid)]
            row = {
                "completed": len(done),
                "tokens_per_s": round(gen / dt, 2),
                "ttft_p99_ms": percentiles(
                    [r.ttft for r in done if r.ttft is not None])["p99"],
                "tpot_p99_ms": percentiles(
                    [t for r in done for t in r.tpot])["p99"],
            }
            return tokens, row, sched.stats()

        prefix_sweep = []
        for frac in (0.0, 0.5, 1.0):
            base_tok, base_row, _ = run_trace(engine, mixed_trace(frac))
            eng_on = ServeEngine(cfg, params, mesh, **geometry,
                                 prefix_cache=True)
            on_tok, on_row, st = run_trace(eng_on, mixed_trace(frac))
            es = eng_on.stats()
            prefix_sweep.append({
                "shared_fraction": frac,
                "baseline": base_row,
                "prefix_cache": on_row,
                "uplift": round(on_row["tokens_per_s"]
                                / base_row["tokens_per_s"], 3),
                "prefix_hit_rate": st["prefix"]["hit_rate"],
                "cow_copies": es["cow_copies"],
                "pool": es["pool"],
                "bitwise_equal_baseline": on_tok == base_tok,
            })
        out["prefix_sweep"] = prefix_sweep

        # Draft-quality sweep at the mixed (0.5) traffic point: every
        # spec engine also has the prefix cache on — the acceptance
        # row IS the "sharing AND speculation" configuration.
        ref_tok, ref_row, _ = run_trace(engine, mixed_trace(0.5))
        acceptance_sweep = []
        for draft in ("ngram:2", "ngram:3", "truncate:1"):
            eng_s = ServeEngine(cfg, params, mesh, **geometry,
                                prefix_cache=True, draft=draft)
            tok, row, st = run_trace(eng_s, mixed_trace(0.5))
            acceptance_sweep.append(dict(
                {"draft": draft, "spec_k": eng_s.spec_k}, **row,
                acceptance_rate=st["spec"]["acceptance_rate"],
                proposed=st["spec"]["proposed"],
                accepted=st["spec"]["accepted"],
                prefix_hit_rate=st["prefix"]["hit_rate"],
                bitwise_equal_baseline=tok == ref_tok))
        out["acceptance_sweep"] = acceptance_sweep
        out["sweep_baseline_tokens_per_s"] = ref_row["tokens_per_s"]
    out["serving"] = serving_stats()
    print(json.dumps(out))
    hvd.shutdown()
    return 0


def fleet_worker_main() -> int:
    """--fleet-worker: the multi-replica phase of `bench.py serve
    --fleet`. Boots every replica engine WARM from the artifact store
    the cold serve worker populated (same mesh, same executables —
    builds==0 is genuine adoption, verified empirically: a
    DESERIALIZED executable is device-bound, so cross-device adoption
    would silently fall back to jit recompiles), then measures
    (a) tokens/s vs replica count (1 -> 2 -> 4) under the shared
    open-loop trace — replicas are stepped on their own threads on
    real backends (``parallel=True``), but SERIALIZED round-robin on
    the CPU virtual mesh, where the host has one core set and XLA
    CPU's collective rendezvous is not reentrant across threads
    sharing devices (concurrent TP steps interleave AllReduce
    participants across run_ids and stall 5s per step) — (b) the
    autoscaler's grow reaction (must land in the same scheduling cycle
    the queue pressure is observed) plus the TTFT on the grown
    replica, (c) the chaos ``replica_kill`` drill at the real router
    dispatch path — zero dropped admitted requests, deterministic
    re-admission order across two identical runs — and (d) the
    fleet-of-1 bitwise gate against a bare scheduler. Prints ONE JSON
    line."""
    import numpy as np_
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.resilience import chaos
    from horovod_tpu.serving import (Request, ServeEngine, ServeScheduler,
                                     ServingFleet, load_for_serving)

    from horovod_tpu.config import knobs

    seed = int(os.environ.get("HVD_SERVE_SEED", "0"))
    n_requests = int(os.environ.get("HVD_FLEET_REQUESTS", "32"))
    rate = float(os.environ.get("HVD_FLEET_RATE", "400"))   # req/s
    ckpt_dir = knobs.get("HOROVOD_CKPT_DIR")
    if not ckpt_dir:
        print("bench.py --fleet-worker: HOROVOD_CKPT_DIR must be set "
              "(the serve parent exports it)", file=sys.stderr)
        return 2

    require_chip("--fleet-worker")
    hvd.init()
    mesh = Mesh(np_.array(jax.devices()), ("tp",))
    tp = int(mesh.shape["tp"])
    # threaded replica stepping needs a reentrant runtime; XLA CPU's
    # collective rendezvous is not (and this host is single-core), so
    # the virtual mesh serializes the replicas round-robin instead
    use_threads = jax.default_backend() != "cpu"
    cfg = tfm.TransformerConfig(
        vocab_size=512, d_model=128, n_heads=max(tp, 8), head_dim=16,
        n_layers=2, d_ff=256, max_seq=512, dtype=jnp.float32,
        dp_axis=None, tp_axis="tp", remat=False)

    def knob_or(name, bench_default):
        return knobs.get(name) if name in os.environ else bench_default
    geometry = dict(
        slots=knob_or("HOROVOD_SERVE_SLOTS", 8),
        page=knob_or("HOROVOD_SERVE_PAGE", 32),
        max_seq=knob_or("HOROVOD_SERVE_MAX_SEQ", 256),
        prefill_chunk=knob_or("HOROVOD_SERVE_PREFILL_CHUNK", 64),
    )

    restored_step, params = load_for_serving(ckpt_dir, mesh, cfg)
    boot_builds = []

    def make_engine(rid):
        # prefix cache ON: the cold sweeps published those executables,
        # so every replica here must construct compile-free
        eng = ServeEngine(cfg, params, mesh, **geometry,
                          prefix_cache=True)
        boot_builds.append(eng.builds)
        return eng

    # half the traffic shares a 64-token system prompt — gives the
    # router's prefix affinity real co-location work
    system_prompt = np_.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, 64).astype(np_.int32)

    def trace(burst=False, n=None):
        n = n_requests if n is None else n
        rng = np_.random.default_rng(seed)
        arrivals = np_.cumsum(rng.exponential(1.0 / rate, n))
        reqs = []
        for i in range(n):
            tail = rng.integers(
                0, cfg.vocab_size,
                int(rng.integers(8, 48))).astype(np_.int32)
            n_new = int(rng.integers(8, 25))
            prompt = (np_.concatenate([system_prompt, tail])
                      if rng.random() < 0.5 else tail)
            reqs.append(Request(rid=i, prompt=prompt,
                                max_new_tokens=n_new,
                                arrival=0.0 if burst
                                else float(arrivals[i])))
        return reqs

    def percentiles(xs):
        if not xs:
            return {"p50": None, "p99": None}
        return {"p50": round(float(np_.percentile(xs, 50)) * 1e3, 3),
                "p99": round(float(np_.percentile(xs, 99)) * 1e3, 3)}

    def fleet_of(n, **kw):
        kw.setdefault("min_replicas", n)
        kw.setdefault("max_replicas", n)
        kw.setdefault("scale_up_depth", 10 ** 9)
        kw.setdefault("scale_down_idle", 10 ** 9)
        kw.setdefault("cooldown", 0)
        kw.setdefault("queue_deadline", 0.0)
        return ServingFleet(make_engine, replicas=n, **kw)

    # ---- fleet-of-1 bitwise vs the bare engine ----------------------------
    # the scheduler's bitwise-solo contract (PR 15) makes tokens
    # independent of batch composition and timing, so the 1-replica
    # scaling row below doubles as the fleet side of this gate
    bare = ServeScheduler(
        ServeEngine(cfg, params, mesh, **geometry, prefix_cache=True),
        mode="continuous", queue_deadline=0.0)
    base_tok = [r.tokens for r in sorted(bare.run(trace()),
                                         key=lambda r: r.rid)]
    fleet_of_1_bitwise = None

    # ---- tokens/s vs replica count (threaded replicas) --------------------
    scaling = []
    for n in (1, 2, 4):
        fl = fleet_of(n)
        t0 = time.perf_counter()
        done = fl.run(trace(), parallel=use_threads)
        dt = time.perf_counter() - t0
        if n == 1:
            fleet_of_1_bitwise = [
                r.tokens for r in sorted(done, key=lambda r: r.rid)
            ] == base_tok
        gen = sum(len(r.tokens) for r in done)
        st = fl.stats()
        scaling.append({
            "replicas": n,
            "completed": len(done),
            "generated_tokens": gen,
            "duration_s": round(dt, 4),
            "tokens_per_s": round(gen / dt, 2),
            "ttft_ms": percentiles([r.ttft for r in done
                                    if r.ttft is not None]),
            "tpot_ms": percentiles([t for r in done for t in r.tpot]),
            "replica_builds": {m: v["builds"]
                               for m, v in st["members"].items()},
            "affinity_hits": st["router"]["affinity_hits"],
        })
    tps = {row["replicas"]: row["tokens_per_s"] for row in scaling}
    speedup_at_2 = round(tps[2] / tps[1], 3) if tps.get(1) else None
    speedup_at_4 = round(tps[4] / tps[1], 3) if tps.get(1) else None
    bottleneck = None
    if speedup_at_2 is not None and speedup_at_2 < 1.6:
        bottleneck = (
            "one host, no spare compute: every replica shares the "
            f"same {tp}-device virtual CPU mesh on a single-core host, "
            "and XLA CPU's collective rendezvous is not reentrant "
            "across threads (concurrent TP decode steps interleave "
            "AllReduce participants and stall), so replica stepping is "
            "SERIALIZED round-robin here — adding replicas adds "
            "scheduling capacity, not compute. Real scaling needs one "
            "TPU slice per replica with threaded stepping "
            "(parallel=True on non-CPU backends; the remeasure "
            "commands).")

    # ---- autoscale drill: grow must land in the observing cycle -----------
    # scale_up_depth=3: the 12-request burst leaves 4 queued after the
    # first replica's 8 slots fill, and the grow condition is STRICT
    # (depth > threshold * ready), so 4 > 3 fires in the observing cycle
    fl = ServingFleet(make_engine, replicas=1, min_replicas=1,
                      max_replicas=2, scale_up_depth=3,
                      scale_down_idle=10 ** 9, cooldown=0,
                      queue_deadline=0.0)
    # two waves: 12 at t=0 trip the grow; 4 FRESH prompts (no resident
    # prefix anywhere, so affinity abstains and JSQ provably picks the
    # empty grown replica) land a beat later while replica 0 is still
    # working its backlog — the grown replica's first token is the
    # scale-up latency the gate measures
    auto_reqs = trace(burst=True, n=16)
    w2 = np_.random.default_rng(seed + 2)
    for r in auto_reqs[12:]:
        r.prompt = w2.integers(0, cfg.vocab_size, 24).astype(np_.int32)
        r.arrival = 0.15
    auto_done = fl.run(auto_reqs)
    grow = next((e for e in fl.scale_events
                 if e["event"] == "grow"
                 and str(e.get("reason", "")).startswith("queue_depth")),
                None)
    grown = fl.replicas.get(grow["replica"]) if grow else None
    ttft_after_grow_ms = None
    if grown is not None and grown.first_token_t is not None:
        ttft_after_grow_ms = round(
            (grown.first_token_t - grow["t"]) * 1e3, 3)
    autoscale = {
        "completed": len(auto_done),
        # burst pressure is visible at cycle 0; the grow event's cycle
        # stamp IS the reaction time in scheduling cycles
        "grow_reaction_cycles": grow["cycle"] if grow else None,
        "ttft_after_grow_ms": ttft_after_grow_ms,
        "warm_replica_builds": grow["builds"] if grow else None,
        "trace": fl.scale_events[:10],
    }

    # ---- chaos replica_kill drill (twice: determinism) --------------------
    def kill_drill():
        chaos.install({"replica_kill": {"replica": 1,
                                        "after_requests": 2}})
        try:
            fl = fleet_of(2)
            reqs = trace(burst=True, n=12)
            done = fl.run(reqs)
            return {"submitted": len(reqs), "completed": len(done),
                    "readmissions": fl.readmissions,
                    "readmission_order": list(fl.readmission_log)}
        finally:
            chaos.install(None)

    k1, k2 = kill_drill(), kill_drill()
    chaos_block = dict(
        k1,
        dropped=k1["submitted"] - k1["completed"],
        deterministic_readmission=(
            k1["readmission_order"] == k2["readmission_order"]))

    out = {
        "phase": "fleet",
        "tp": tp,
        "parallel_replica_threads": use_threads,
        "restored_step": restored_step,
        "geometry": geometry,
        "n_requests": n_requests,
        "rate": rate,
        "fleet_of_1_bitwise": fleet_of_1_bitwise,
        "scaling": scaling,
        "speedup_at_2": speedup_at_2,
        "speedup_at_4": speedup_at_4,
        "bottleneck": bottleneck,
        "autoscale": autoscale,
        "chaos": chaos_block,
        "replica_boot_builds": boot_builds,
    }
    print(json.dumps(out))
    hvd.shutdown()
    return 0


def serve_main() -> int:
    """`bench.py serve`: the serving latency/throughput artifact
    (ROADMAP item 1). Spawns --serve-worker twice against ONE artifact
    store + checkpoint dir: the COLD replica commits a training
    snapshot, hands it off to serving, publishes every serve executable,
    measures open-loop Poisson traffic under continuous batching vs
    the static-batch baseline, then runs the hvdspec sweeps — prefix
    hit rate over the shared-system-prompt fraction and acceptance
    rate over the draft-quality knob, each gated bitwise against the
    cache-off engine on the identical trace; the WARM replica is a
    fresh process that boots with prefix caching AND speculation on
    and must reach its first token with ZERO builder invocations (the
    BENCH_TTFS warm-boot gate applied to serving). Commits
    BENCH_SERVE.json and appends the serve point to the goodput
    ledger; exits 1 when any gate fails.

    With ``--fleet`` a third worker runs the multi-replica phase
    against the SAME store: tokens/s vs replica count, the autoscale
    reaction drill, and the chaos ``replica_kill`` drill — merged into
    BENCH_SERVE.json as the ``fleet`` block, with its own gates and a
    ``serve_fleet`` ledger record (the regression sentinel's fleet
    axis)."""
    import tempfile

    fleet_mode = "--fleet" in sys.argv
    here = os.path.dirname(os.path.abspath(__file__))
    workdir = tempfile.mkdtemp(prefix="hvdserve-bench-")
    env = _worker_env()
    env.update(
        HOROVOD_ARTIFACT_STORE=os.path.join(workdir, "store"),
        HOROVOD_CKPT_DIR=os.path.join(workdir, "ckpt"),
        HOROVOD_GOODPUT_LEDGER=os.path.join(workdir, "ledger.jsonl"),
    )

    def run(phase: str) -> dict:
        child_env = dict(env, HVD_SERVE_PHASE=phase,
                         HVD_T0=repr(time.time()))
        flag = "--fleet-worker" if phase == "fleet" else "--serve-worker"
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            env=child_env, capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            print(proc.stdout, file=sys.stderr)
            print(proc.stderr, file=sys.stderr)
            raise RuntimeError(
                f"bench.py serve: {phase} worker exited "
                f"{proc.returncode}")
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
        raise RuntimeError(
            f"bench.py serve: no JSON line from the {phase} worker")

    try:
        cold = run("cold")
        warm = run("warm")
        fleet = run("fleet") if fleet_mode else None
        ledger_lines = []
        try:
            with open(env["HOROVOD_GOODPUT_LEDGER"]) as f:
                for line in f:
                    try:
                        ledger_lines.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            pass
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    errors = []
    cont = cold["continuous"]
    stat = cold.get("static") or {}
    n_req = cont.get("completed")
    if cont.get("completed", 0) <= 0:
        errors.append("no requests completed under continuous batching")
    for block, name in ((cont, "continuous"), (stat, "static")):
        for metric in ("ttft_ms", "tpot_ms"):
            pcts = block.get(metric) or {}
            if pcts.get("p50") is not None and pcts.get("p99") is not None \
                    and pcts["p50"] > pcts["p99"]:
                errors.append(f"{name} {metric} p50 {pcts['p50']} > "
                              f"p99 {pcts['p99']}")
    occ = cont.get("batch_occupancy")
    if not (occ and 0 < occ <= 1):
        errors.append(f"continuous batch occupancy {occ} not in (0, 1]")
    if stat and cont.get("tokens_per_s", 0) <= stat.get(
            "tokens_per_s", float("inf")):
        errors.append(
            f"continuous batching ({cont.get('tokens_per_s')} tok/s) "
            f"did not beat the static-batch baseline "
            f"({stat.get('tokens_per_s')} tok/s) at the same traffic")
    if warm.get("builds") != 0:
        errors.append(
            f"warm serving boot invoked the builder "
            f"{warm.get('builds')} time(s); the artifact store must "
            f"serve every prefill/decode/verify/draft/COW executable "
            f"(outcomes: {warm.get('store_outcomes')})")
    if any(v != "hit" for v in (warm.get("store_outcomes") or {}).values()):
        errors.append(f"warm store outcomes not all hits: "
                      f"{warm.get('store_outcomes')}")
    warm_labels = set(warm.get("store_outcomes") or {})
    for needle in ("serve_verify_", "serve_draft_", "serve_cow_copy"):
        if not any(k.startswith(needle) for k in warm_labels):
            errors.append(
                f"warm boot adopted no {needle}* executable — the "
                f"hvdspec surface must be store-served too "
                f"(labels: {sorted(warm_labels)})")

    # hvdspec sweep gates: sharing must be exact (bitwise vs the
    # cache-off baseline on the identical trace), the hit rate must
    # respond to the traffic mix, and fully-shared traffic must come
    # out faster than the PR 15 cache-off engine.
    psweep = cold.get("prefix_sweep") or []
    asweep = cold.get("acceptance_sweep") or []
    by_frac = {r["shared_fraction"]: r for r in psweep}
    if set(by_frac) != {0.0, 0.5, 1.0}:
        errors.append(f"prefix sweep fractions {sorted(by_frac)} != "
                      f"[0.0, 0.5, 1.0]")
    for row in psweep:
        if not row.get("bitwise_equal_baseline"):
            errors.append(
                f"prefix cache changed tokens at shared_fraction="
                f"{row['shared_fraction']} — sharing must be bitwise "
                f"invisible")
        if row["prefix_cache"].get("completed") != n_req:
            errors.append(
                f"prefix sweep row {row['shared_fraction']} completed "
                f"{row['prefix_cache'].get('completed')} of the trace")
    if by_frac and not (by_frac[1.0]["prefix_hit_rate"]
                        > by_frac[0.0]["prefix_hit_rate"]):
        errors.append(
            f"prefix hit rate did not rise with the shared fraction "
            f"({by_frac[0.0]['prefix_hit_rate']} at 0.0 vs "
            f"{by_frac[1.0]['prefix_hit_rate']} at 1.0)")
    if by_frac and not by_frac[1.0]["uplift"] > 1.0:
        errors.append(
            f"prefix cache uplift {by_frac[1.0]['uplift']}x at "
            f"shared_fraction=1.0 did not beat the cache-off engine")
    for row in asweep:
        if not row.get("bitwise_equal_baseline"):
            errors.append(
                f"speculative decode ({row['draft']}) changed tokens — "
                f"accept-prefix verification must be bitwise exact")
        if not (0.0 <= row.get("acceptance_rate", -1.0) <= 1.0):
            errors.append(f"{row['draft']} acceptance rate "
                          f"{row.get('acceptance_rate')} not in [0, 1]")
        if row.get("completed") != n_req:
            errors.append(f"acceptance row {row['draft']} completed "
                          f"{row.get('completed')} of the trace")
    if len(asweep) != 3:
        errors.append(f"acceptance sweep has {len(asweep)} rows; "
                      f"expected ngram:2, ngram:3, truncate:1")
    if not any((rec.get("serve") or {}).get("scheduler", {}).get(
            "completed") for rec in ledger_lines):
        errors.append("goodput ledger carries no serve record block")

    # ---- fleet gates (--fleet) ------------------------------------------
    fleet_rows = {}
    if fleet_mode:
        fl = fleet or {}
        fleet_rows = {int(r["replicas"]): r
                      for r in (fl.get("scaling") or [])}
        if sorted(fleet_rows) != [1, 2, 4]:
            errors.append(f"fleet scaling measured replica counts "
                          f"{sorted(fleet_rows)} != [1, 2, 4]")
        for n, row in sorted(fleet_rows.items()):
            if row.get("completed") != fl.get("n_requests"):
                errors.append(
                    f"fleet row {n} completed {row.get('completed')} "
                    f"of {fl.get('n_requests')} requests")
            cold_builds = {m: b for m, b in
                           (row.get("replica_builds") or {}).items()
                           if b != 0}
            if cold_builds:
                errors.append(
                    f"fleet row {n}: replica(s) booted with builder "
                    f"invocations {cold_builds} — every replica after "
                    f"the cold publish must construct warm")
        if not fl.get("fleet_of_1_bitwise"):
            errors.append("fleet of 1 is not bitwise-identical to the "
                          "bare engine on the identical trace")
        sp2 = fl.get("speedup_at_2")
        if sp2 is None:
            errors.append("no 2-replica speedup measured")
        elif sp2 < 1.6 and not fl.get("bottleneck"):
            errors.append(f"fleet speedup at 2 replicas {sp2}x < 1.6x "
                          f"with no bottleneck named")
        auto = fl.get("autoscale") or {}
        react = auto.get("grow_reaction_cycles")
        if react is None or react > 1:
            errors.append(f"autoscaler did not grow within one "
                          f"scheduling cycle of the queue pressure "
                          f"(reaction: {react} cycles)")
        if auto.get("warm_replica_builds") != 0:
            errors.append(
                f"autoscale grow invoked the builder "
                f"{auto.get('warm_replica_builds')} time(s); scale-up "
                f"must ride the artifact store's serve kind")
        if auto.get("ttft_after_grow_ms") is None:
            errors.append("grown replica served no token — no "
                          "TTFT-after-grow measured")
        ch = fl.get("chaos") or {}
        if ch.get("dropped") != 0:
            errors.append(f"replica_kill drill dropped "
                          f"{ch.get('dropped')} admitted request(s)")
        if not ch.get("readmissions"):
            errors.append("replica_kill drill re-admitted nothing — "
                          "the chaos hook did not fire at the router "
                          "dispatch path")
        if not ch.get("deterministic_readmission"):
            errors.append("replica_kill re-admission order differed "
                          "across two identical runs")

    artifact = {
        "metric": "serve_open_loop_latency_throughput",
        "unit": "ms (TTFT/TPOT percentiles), tokens/s",
        "workload": "TransformerLM 2L/d128 TP-sharded over the 8-device "
                    "virtual CPU mesh; paged KV cache, chunked prefill, "
                    "greedy decode; open-loop Poisson traffic "
                    "(24 requests, ~200 req/s, prompts 8-48, 8-24 new "
                    "tokens); hvdspec sweeps mix in a 64-token shared "
                    "system prompt and run every draft mode with the "
                    "prefix cache on",
        "geometry": cold.get("geometry"),
        "continuous": cont,
        "static_baseline": stat,
        "continuous_vs_static_speedup": (
            round(cont["tokens_per_s"] / stat["tokens_per_s"], 3)
            if stat.get("tokens_per_s") else None),
        "prefix_sweep": cold.get("prefix_sweep"),
        "acceptance_sweep": cold.get("acceptance_sweep"),
        "warm_boot": {
            "builds": warm.get("builds"),
            "store_outcomes": warm.get("store_outcomes"),
            "ttfb_boot_s": warm.get("ttfb_boot_s"),
            "cold_ttfb_boot_s": cold.get("ttfb_boot_s"),
            "restored_step": warm.get("restored_step"),
        },
        "gates": {"errors": errors},
        "chip": "cpu (virtual 8-device mesh)",
        "remeasure_commands": [
            "python bench.py serve",
            "JAX_PLATFORMS=tpu python bench.py serve",
            "JAX_PLATFORMS=tpu HOROVOD_SERVE_SLOTS=32 "
            "HOROVOD_SERVE_PAGE=128 python bench.py serve",
            "JAX_PLATFORMS=tpu HOROVOD_SERVE_PREFIX_CACHE=1 "
            "HOROVOD_SERVE_DRAFT=truncate:1 HOROVOD_SERVE_SPEC_K=4 "
            "python bench.py serve",
            "JAX_PLATFORMS=tpu HOROVOD_SERVE_PREFIX_CACHE=1 "
            "HOROVOD_SERVE_DRAFT=ngram:3 HOROVOD_SERVE_SLOTS=32 "
            "python bench.py serve",
        ],
    }
    path = os.path.join(here, "BENCH_SERVE.json")
    if fleet_mode:
        artifact["fleet"] = {
            "workload": f"{fleet.get('n_requests')} open-loop requests "
                        f"(~{fleet.get('rate'):g} req/s Poisson, 50% "
                        f"sharing a 64-token system prompt) through the "
                        f"prefix-affinity router; every replica is a "
                        f"full engine (own KV pool) booted warm from "
                        f"the shared store"
                        + (", stepped on its own thread"
                           if fleet.get("parallel_replica_threads")
                           else "; replica stepping is serialized "
                                "round-robin on the CPU virtual mesh "
                                "(see bottleneck)"),
            "parallel_replica_threads": fleet.get(
                "parallel_replica_threads"),
            "scaling": fleet.get("scaling"),
            "speedup_at_2": fleet.get("speedup_at_2"),
            "speedup_at_4": fleet.get("speedup_at_4"),
            "bottleneck": fleet.get("bottleneck"),
            "fleet_of_1_bitwise": fleet.get("fleet_of_1_bitwise"),
            "autoscale": fleet.get("autoscale"),
            "chaos": fleet.get("chaos"),
            "replica_boot_builds": fleet.get("replica_boot_builds"),
            "remeasure_commands": [
                "python bench.py serve --fleet",
                "JAX_PLATFORMS=tpu python bench.py serve --fleet",
                "JAX_PLATFORMS=tpu HOROVOD_FLEET_MAX_REPLICAS=8 "
                "HVD_FLEET_REQUESTS=256 HVD_FLEET_RATE=2000 "
                "python bench.py serve --fleet",
                "JAX_PLATFORMS=tpu HOROVOD_FLEET_AFFINITY=0 "
                "python bench.py serve --fleet",
            ],
        }
    else:
        # plain `serve` must not erase a committed fleet block: carry
        # the previous measurement forward (merge, not overwrite)
        try:
            with open(path, encoding="utf-8") as f:
                prev = json.load(f)
            if "fleet" in prev:
                artifact["fleet"] = prev["fleet"]
        except (OSError, ValueError):
            pass
    with open(path + ".tmp", "w") as f:
        json.dump(artifact, f, indent=1)
    os.replace(path + ".tmp", path)
    psweep_by = {r["shared_fraction"]: r for r in (cold.get(
        "prefix_sweep") or [])}
    summary = {
        "metric": "serve_continuous_vs_static",
        "continuous_tokens_per_s": cont.get("tokens_per_s"),
        "static_tokens_per_s": stat.get("tokens_per_s"),
        "ttft_ms": cont.get("ttft_ms"),
        "tpot_ms": cont.get("tpot_ms"),
        "occupancy": occ,
        "prefix_uplift_shared_1.0": (psweep_by.get(1.0) or {}).get(
            "uplift"),
        "acceptance_rates": {r["draft"]: r["acceptance_rate"]
                             for r in (cold.get("acceptance_sweep")
                                       or [])},
        "warm_builds": warm.get("builds"),
        "errors": errors,
        "artifact": path,
    }
    # the serve point enters the cross-run history the regression
    # sentinel's serving axis reads (no-op when no ledger is configured)
    from horovod_tpu.goodput import ledger as goodput_ledger
    goodput_ledger.append_record(bench=summary)
    if fleet_mode:
        peak = fleet_rows[max(fleet_rows)] if fleet_rows else {}
        fleet_summary = {
            "metric": "serve_fleet",
            "fleet_tokens_per_s": peak.get("tokens_per_s"),
            "ttft_after_grow_ms": (fleet.get("autoscale") or {}).get(
                "ttft_after_grow_ms"),
            "speedup_at_2": fleet.get("speedup_at_2"),
            "replicas_measured": sorted(fleet_rows),
            "readmissions": (fleet.get("chaos") or {}).get(
                "readmissions"),
            "errors": errors,
            "artifact": path,
        }
        # second record: the fleet axis of the regression sentinel
        goodput_ledger.append_record(bench=fleet_summary)
        summary["fleet"] = {
            k: fleet_summary[k]
            for k in ("fleet_tokens_per_s", "speedup_at_2",
                      "ttft_after_grow_ms")}
    print(json.dumps(summary))
    if errors:
        for e in errors:
            print(f"bench.py serve: {e}", file=sys.stderr)
        return 1
    return 0


def regression_report_main() -> int:
    """--regression-report: the cross-run regression sentinel — a
    pass/regress verdict over the BENCH_r<N>.json rounds beside this
    file (none committed: no chip record yet, the axis reports skipped)
    and the HOROVOD_GOODPUT_LEDGER history (goodput/ledger.py schema).
    Exit 0 = pass, 1 = regress (the CI gate), 2 = nothing to judge."""
    from horovod_tpu.goodput import ledger as goodput_ledger
    here = os.path.dirname(os.path.abspath(__file__))
    report = goodput_ledger.regression_report(here)
    print(json.dumps(report))
    statuses = {c["status"] for c in report["checks"]}
    if statuses == {"skipped"}:
        print("bench.py --regression-report: no BENCH rounds and no "
              "ledger records to judge", file=sys.stderr)
        return 2
    return 1 if report["verdict"] == "regress" else 0


if __name__ == "__main__":
    if not cpu_by_name():
        # Chip runs share one persistent compile cache; children inherit
        # it through the environment. The CPU-by-name modes are CI gates
        # with their own cold/warm compile A/Bs and stay uncached.
        from horovod_tpu.utils import compile_cache
        compile_cache.place(os.path.dirname(os.path.abspath(__file__)))
    if "--serve-worker" in sys.argv:
        sys.exit(serve_worker_main())
    if "--fleet-worker" in sys.argv:
        sys.exit(fleet_worker_main())
    if "serve" in sys.argv[1:]:
        sys.exit(serve_main())
    if "--store-worker" in sys.argv:
        sys.exit(store_worker_main())
    if "--store-report" in sys.argv:
        sys.exit(store_report_main())
    if "--regression-report" in sys.argv:
        sys.exit(regression_report_main())
    if "--goodput-smoke" in sys.argv:
        sys.exit(goodput_smoke_main())
    if "--trace-report" in sys.argv:
        sys.exit(trace_report_main())
    if "--cost-report" in sys.argv:
        sys.exit(cost_report_main())
    if "--compat-report" in sys.argv:
        sys.exit(compat_report_main())
    if "--verify-report" in sys.argv:
        sys.exit(verify_report_main())
    if "--overlap-report" in sys.argv:
        sys.exit(overlap_report_main())
    if "--divergence-overhead" in sys.argv:
        sys.exit(divergence_overhead_main())
    if "--pallas-bandwidth" in sys.argv:
        sys.exit(pallas_bandwidth_main())
    if "transformer" in sys.argv[1:]:
        sys.exit(transformer_main())
    if "--scaling-worker" in sys.argv:
        sys.exit(_scaling_worker())
    if "--collectives-worker" in sys.argv:
        sys.exit(_collectives_worker())
    if "--collectives" in sys.argv:
        sys.exit(collectives_main())
    if "--project" in sys.argv:
        sys.exit(project_main())
    if "--scaling" in sys.argv:
        sys.exit(scaling_main())
    sys.exit(main())
