"""Arithmetic shared by the per-layer metric readers in ``metrics/``. Each
returns None where there is nothing to read, never 0 for a share."""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional

from benchmarks.lib import window
from benchmarks.roofline import flash_attention as flash_cost

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "f64": 8, "pred": 1, "s64": 8, "u64": 8}
_SHAPE_RE = re.compile(r"\b(f32|bf16|f16|f64|s32|u32|s64|u64|s8|u8|pred)"
                       r"\[([0-9,]*)\]")
_ALLREDUCE_RE = re.compile(
    r"=\s*(\(?[^=]*?\)?)\s+all-reduce(?:-start)?\(")


def step_ms_p50(run) -> Optional[float]:
    return window.median(run.unit_s) * 1e3 if run.unit_s else None


def step_ms_max_over_p50(run) -> Optional[float]:
    return max(run.unit_s) / window.median(run.unit_s) if run.unit_s else None


def train_mfu(run) -> Optional[float]:
    """Required operations of the counted steps over the window's time, over
    the chips' bf16 peak, in percent."""
    if not run.unit_s:
        return None
    return share_of_peak(
        run, run.program["required_flops_per_step"] * len(run.unit_s))


def share_of_peak(run, flops: float) -> Optional[float]:
    """``flops`` over the window's time, over the chips' bf16 peak, in
    percent."""
    if not flops or not run.peak:
        return None
    return 100.0 * flops / run.elapsed_s / (
        run.device["count"] * run.peak["flops_bf16"])


def step_hbm_gb(run) -> Optional[float]:
    """Arguments + temporaries of the compiled step (the outputs alias the
    donated state), per chip, from the compiler's ``memory_analysis``."""
    if "temp_bytes" not in run.program:
        return None
    return (run.program["argument_bytes"] + run.program["temp_bytes"]) / 1e9


def flash_roofline(run, kernel: str, cost: Callable) -> Optional[float]:
    if run.trace is None or not run.peak:
        return None
    seconds = run.trace.kernel_s(kernel)
    if not seconds:
        return None
    s = run.program["shapes"]
    least = flash_cost.least_seconds(
        cost(s["batch"], s["heads"], s["seq"], s["head_dim"]), run.peak)
    return 100.0 * least * run.trace.kernel_calls(kernel) / seconds


def flash_ms_per_step(run) -> Optional[float]:
    if run.trace is None:
        return None
    kernels = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
    seconds = [run.trace.kernel_s(k) for k in kernels]
    if not all(seconds):
        return None
    steps = run.trace.kernel_calls(kernels[0]) / run.program["shapes"]["layers"]
    return 1e3 * sum(seconds) / steps


def span_ms_p50(run, name: str) -> Optional[float]:
    """Median milliseconds of the host spans of that name in the traced
    window."""
    if run.trace is None:
        return None
    seconds = run.trace.span_seconds(name)
    return 1e3 * window.median(seconds) if seconds else None


def scope_ms_per_run(run, program: str, scopes: Dict[str, str], part: str
                     ) -> Optional[float]:
    """Own device milliseconds under one scope per run of the programs whose
    name contains ``program``. ``scopes``: part of the metric's name -> the
    ``jax.named_scope`` it reads; the part ``other`` is the rest of the
    program's device time, under another scope or under none."""
    if part != "other" and part not in scopes:
        raise ValueError(f"no scope for the part {part!r} (have "
                         f"{sorted(scopes)} and 'other')")
    if run.trace is None:
        return None
    split = run.trace.scope_seconds(program, tuple(scopes.values()))
    runs = run.trace.program_runs(program)
    if split is None or not runs:
        return None
    seconds = split[scopes.get(part, "other")]
    return 1e3 * seconds / runs if seconds else None


def allreduce_bytes(hlo_text: str) -> int:
    """Bytes of the results of every all-reduce in a compiled HLO module."""
    total = 0
    for m in _ALLREDUCE_RE.finditer(hlo_text):
        for dtype, dims in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dtype]
    return total
