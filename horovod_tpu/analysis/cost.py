"""``hvd.cost_report`` — the HVD7xx driver: compile a real step
function from abstract args and run the resource model on its HLO.

Fourth analysis tier, same shape as the three before it: the step is
lowered and AOT-compiled from ``jax.ShapeDtypeStruct`` args (nothing
executes, no memory is materialized — a multi-B-param config costs a
compile, not a chip), then :mod:`rules_cost`'s stdlib model walks the
optimized text: per-instruction HBM traffic with tile padding, a
buffer-liveness pass for peak per-device memory, the re-stream
detector, and a roofline projection against committed rates. Findings
ride the same Finding/fingerprint/suppression pipeline as every other
tier (``# hvdlint: disable=HVD70x`` on the step's def line works), and
``hvdlint --cost module:target`` resolves the exact target format
``--ir`` uses.

Calibration status (what the numbers mean on the CPU virtual mesh) is
documented in docs/analysis.md — in particular the two corrections the
driver applies and records in the report: the CPU backend legalizes
bf16 compute to f32 (intermediates are charged at declared width, the
``corrections`` block says so), and loop bodies are counted once and
rescaled by the executable's own flop count when ``while`` ops are
present (``projection.scale``).
"""

from __future__ import annotations

import contextlib
import hashlib
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from horovod_tpu.analysis import rules_cost
from horovod_tpu.analysis.engine import Finding
from horovod_tpu.analysis.ir import (
    VerifyTarget, _anchor, _args_signature, _suppressed, resolve_targets)

# Committed-measurement defaults (SCALING.json cost_model_rates carries
# the same numbers with provenance): XLA's fused-elementwise streaming
# rate measured in PERF.md r5 (585 GB/s), the realized conv-fusion MXU
# rate from the r2 profile (144 TF/s at 73% occupancy), and the
# single-direction ICI ring rate the tier model uses.
DEFAULT_RATES: Dict[str, float] = {
    "hbm_gb_s": 585.0,
    "matmul_flop_s": 1.44e14,
    "ici_gb_s": 100.0,
}

_OPT_STATE_RE = re.compile(
    r"opt_state|\bmu\b|\bnu\b|momentum|trace|velocity|accum", re.I)
_PARAMS_RE = re.compile(r"param|batch_stats|kernel|embedding", re.I)


def _default_categorize(label: str) -> str:
    if _OPT_STATE_RE.search(label):
        return "opt_state"
    if _PARAMS_RE.search(label):
        return "params"
    return "other"


def _per_device_bytes(leaf: Any, sharding: Any) -> Optional[int]:
    import numpy as np
    shape = tuple(getattr(leaf, "shape", ()) or ())
    dtype = getattr(leaf, "dtype", None)
    itemsize = int(getattr(dtype, "itemsize", None) or 4)
    try:
        shard = sharding.shard_shape(shape)
        return int(np.prod(shard, dtype=np.int64)) * itemsize \
            if shard else itemsize
    except Exception:
        return None


def cost_report(step_fn: Any, args: Sequence[Any], *,
                mesh: Any = None,
                name: str = "",
                tag: Optional[str] = None,
                compute_dtype: Optional[str] = None,
                hbm_budget_bytes: Optional[int] = None,
                data_axes: Optional[Sequence[str]] = None,
                categorize: Optional[Callable[[str], str]] = None,
                measured_ms: Optional[float] = None,
                measured_source: str = "",
                rates: Optional[Dict[str, float]] = None,
                donate_argnums: Optional[Tuple[int, ...]] = None,
                ) -> Tuple[List[Finding], dict]:
    """Compile ``step_fn(*args)`` (abstract args — nothing executes) and
    return ``(findings, report)``: HVD701-705 findings plus the full
    resource report ``bench.py --cost-report`` commits to COST.json.

    - ``compute_dtype``: the step's declared compute dtype (``"bf16"``);
      on backends that legalize it to f32 the model charges f32
      intermediates at the declared width (recorded in
      ``report["corrections"]``).
    - ``hbm_budget_bytes``: HVD702 budget (default
      ``HOROVOD_COST_HBM_GB``).
    - ``data_axes``: mesh axes the batch is sharded over — HVD704 fires
      for large optimizer-state leaves replicated across them.
    - ``categorize``: ``keystr(leaf path) -> {"params","opt_state",
      "other"}`` for the memory breakdown (a heuristic default matches
      flax/optax naming).
    - ``measured_ms``/``measured_source``: the committed measured step
      time HVD705 compares the projection against (no measurement — no
      HVD705 verdict, reported as such).
    - ``rates``: roofline rates (default: the committed SCALING.json
      cost_model_rates numbers).
    """
    import jax

    from horovod_tpu.config import knobs

    path, line, symbol = _anchor(step_fn, name)
    name = name or symbol
    findings: List[Finding] = []
    report: dict = {"step": name, "path": path, "line": line}

    def add(code: str, message: str) -> None:
        rule = rules_cost.RULES_BY_CODE[code]
        if _suppressed(step_fn, code):
            report.setdefault("suppressed", []).append(code)
            return
        findings.append(Finding(code, rule.severity, path, line, 1,
                                f"step '{name}': {message}", symbol))

    args = tuple(args)
    tag = tag or f"{symbol}@{_args_signature(args)}"
    report["tag"] = tag
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        jitted = step_fn if hasattr(step_fn, "lower") else \
            jax.jit(step_fn, donate_argnums=donate_argnums or ())
        lowered = jitted.lower(*args)
        import time as _time
        _t0 = _time.perf_counter()
        compiled = lowered.compile()
        from horovod_tpu.goodput import accountant as _goodput
        _goodput.carve(_goodput.COMPILE, _time.perf_counter() - _t0)

    hlo = compiled.as_text()
    report["fingerprint"] = hashlib.sha1(
        rules_cost.strip_source_info(hlo).encode()).hexdigest()[:12]
    comps, entry = rules_cost.parse_computations(hlo)

    # ---- corrections: backend dtype legalization + loop trip counts -----
    platform = getattr(jax.devices()[0], "platform", "")
    declared = rules_cost._HLO_DTYPE_BYTES.get(compute_dtype or "", 4) \
        if compute_dtype else 4
    dtype_scale: Dict[str, float] = {}
    if declared < 4 and platform == "cpu":
        dtype_scale["f32"] = declared / 4.0
    rows, totals = rules_cost.fusion_table(hlo, dtype_scale=dtype_scale)
    report["totals"] = totals
    loop_scale = 1.0
    has_while = any(
        i.op == "while" for c in comps.values() for i in c)
    if has_while and totals["flops"]:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            xla_flops = float(ca.get("flops", 0.0)) if ca else 0.0
            if xla_flops > 1.5 * totals["flops"]:
                loop_scale = xla_flops / totals["flops"]
        except Exception:
            pass
    report["corrections"] = {
        "f32_width_scale": dtype_scale.get("f32", 1.0),
        "reason": ("backend legalizes the declared compute dtype "
                   f"'{compute_dtype}' to f32; f32 intermediates are "
                   "charged at declared width" if dtype_scale else "none"),
        "loop_scale": round(loop_scale, 3),
    }

    # ---- per-leaf argument table (exact, from the executable) -----------
    flat = jax.tree_util.tree_flatten_with_path(args)[0]
    leaves = [x for _, x in flat]
    labels = [jax.tree_util.keystr(kp) or f"[{i}]"
              for i, (kp, _) in enumerate(flat)]
    cat = categorize or _default_categorize
    shardings: List[Any] = []
    try:
        in_sh = compiled.input_shardings
        sh_leaves = jax.tree_util.tree_leaves(in_sh[0]) + \
            jax.tree_util.tree_leaves(in_sh[1])
        if len(sh_leaves) == len(leaves):
            shardings = sh_leaves
    except Exception:
        shardings = []
    from horovod_tpu.analysis.ir import _leaf_bytes
    leaf_table: List[dict] = []
    for i, (label, leaf) in enumerate(zip(labels, leaves)):
        logical = _leaf_bytes(leaf)
        per_dev = None
        if shardings:
            per_dev = _per_device_bytes(leaf, shardings[i])
        leaf_table.append({
            "label": label, "category": cat(label),
            "logical_bytes": logical,
            "per_device_bytes": per_dev if per_dev is not None else logical,
            "sharding_known": per_dev is not None,
        })
    by_cat: Dict[str, int] = {"params": 0, "opt_state": 0, "other": 0}
    for l in leaf_table:
        by_cat[l["category"]] = by_cat.get(l["category"], 0) \
            + l["per_device_bytes"]

    # ---- liveness: transient peak over the scheduled entry --------------
    lv = rules_cost.liveness(comps.get(entry, ()), dtype_scale=dtype_scale)
    args_total = sum(l["per_device_bytes"] for l in leaf_table)
    accounting = {
        "params_bytes": by_cat.get("params", 0),
        "opt_state_bytes": by_cat.get("opt_state", 0),
        "other_arg_bytes": by_cat.get("other", 0),
        "transient_peak_bytes": lv["peak_bytes"],
        "peak_bytes": args_total + lv["peak_bytes"],
        "top_transients": lv["top_buffers"],
        "sharding_known": bool(shardings),
    }
    report["accounting"] = accounting
    report["leaves"] = sorted(
        leaf_table, key=lambda l: -l["per_device_bytes"])[:16]

    # ---- re-stream detector + BN-phase traffic --------------------------
    min_rs_bytes = int(knobs.get("HOROVOD_COST_RESTREAM_MIN_BYTES"))
    min_rs_reads = int(knobs.get("HOROVOD_COST_RESTREAM_READS"))
    rs = rules_cost.restreamed(comps.get(entry, ()), min_rs_bytes,
                               min_rs_reads)

    def _row_scale(row: dict) -> float:
        dtype = row["shape"].split("[", 1)[0].split("/")[0]
        return dtype_scale.get(dtype, 1.0)

    bn_bytes = sum(r["reads"] * r["bytes_padded"] * _row_scale(r)
                   for r in rs)
    use_rates = dict(DEFAULT_RATES)
    use_rates.update(rates or {})
    bn_ms = bn_bytes / (use_rates["hbm_gb_s"] * 1e9) * 1e3
    report["restreamed"] = rs[:12]
    report["bn_phase"] = {
        "bytes": int(bn_bytes),
        "ms": round(bn_ms, 2),
        "definition": ("sum over re-streamed intermediates of "
                       "reads x padded bytes (producer write excluded: "
                       "it belongs to the producing matmul/conv), at "
                       "declared compute width"),
    }

    # ---- roofline projection --------------------------------------------
    projection = rules_cost.project_times(rows, use_rates,
                                          scale=loop_scale)
    # CPU-backend fusion granularity inflates byte counts vs a TPU
    # lowering of the same step (every producer->conv edge is a
    # separate HBM round trip here; TPU fuses it into the MXU
    # pipeline), so the calibrated step-time model takes the matmul
    # term at the flop roofline — r2 measured the convs MXU-bound at
    # 144 TF/s — plus the re-stream (BN-phase) traffic and ring
    # collectives. The per-class max-roofline sums stay in the report
    # as the pessimistic bound (docs/analysis.md#cost-model).
    matmul_flops_ms = (totals["flops"] * loop_scale
                       / use_rates["matmul_flop_s"]) * 1e3
    model_ms = (matmul_flops_ms
                + projection["classes"]["collective"]["ms"] + bn_ms)
    projection["step_ms_model"] = round(model_ms, 2)
    projection["step_ms_composition"] = \
        "matmul_flops + bn_restream + ring_collectives"
    projection["matmul_flops_ms"] = round(matmul_flops_ms, 2)
    projection["stream_ms_upper_bound"] = \
        projection["classes"]["stream"]["ms"]
    report["projection"] = projection

    # ---- HVD701-705 -----------------------------------------------------
    pad_amp = float(knobs.get("HOROVOD_COST_PAD_AMPLIFICATION"))
    pad_waste = int(knobs.get("HOROVOD_COST_PAD_MIN_WASTE"))
    for p in rules_cost.check_padding(rows, pad_amp, pad_waste):
        add("HVD701", p["message"])
    budget = hbm_budget_bytes if hbm_budget_bytes is not None else \
        int(float(knobs.get("HOROVOD_COST_HBM_GB")) * 2 ** 30)
    accounting["budget_bytes"] = budget
    for p in rules_cost.check_oom(accounting, budget):
        add("HVD702", p["message"])
    for p in rules_cost.check_restream(rs):
        add("HVD703", p["message"])
    axes = tuple(data_axes or ())
    if not axes and mesh is not None:
        try:
            axes = tuple(str(a) for a in mesh.axis_names
                         if mesh.shape[a] > 1)
        except Exception:
            axes = ()
    min_repl = int(knobs.get("HOROVOD_COST_REPLICATED_MIN_BYTES"))
    if shardings:                  # exact shardings only: no guessing
        for p in rules_cost.check_replicated(leaf_table, min_repl, axes):
            add("HVD704", p["message"])
    if measured_ms is not None:
        tol = float(knobs.get("HOROVOD_COST_ROOFLINE_TOL"))
        fake = {"total_ms": model_ms}
        for p in rules_cost.check_roofline(fake, measured_ms,
                                           measured_source, tol):
            add("HVD705", p["message"])
        report["measured"] = {"ms": measured_ms,
                              "source": measured_source,
                              "ratio": round(model_ms / measured_ms, 3)
                              if measured_ms else None}
    else:
        report["measured"] = None

    report["findings"] = [f.to_dict() for f in findings]
    return findings, report


def cost_targets(specs: Sequence[str]) -> List[Finding]:
    """Run :func:`cost_report` over every ``--cost`` target spec (the
    same ``module:callable`` format as ``--ir``; the target's
    ``options`` dict is forwarded — ``hbm_budget_bytes``,
    ``measured_ms``, ``rates``, ...) and merge the findings into the
    shared baseline/suppression/output pipeline."""
    findings: List[Finding] = []
    for spec in specs:
        for t in resolve_targets(spec):
            fs, _ = cost_report(t.step_fn, t.args, mesh=t.mesh,
                                name=t.name, **t.options)
            findings.extend(fs)
    return findings


__all__ = ["cost_report", "cost_targets", "DEFAULT_RATES",
           "VerifyTarget"]
