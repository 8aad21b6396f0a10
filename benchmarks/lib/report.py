"""What a run hands from the kind that drove it to the metric readers and
to the result line."""

from __future__ import annotations

import csv
import dataclasses
import importlib.util
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.lib.cell import BENCH_DIR, ROOT, Cell


@dataclasses.dataclass
class RunRecord:
    cell: Cell
    seed: int
    device: Dict[str, Any]              # platform, kind, count, memory_peak_bytes
    peak: Dict[str, float]              # the chip's published peaks
    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    setup: Dict[str, float] = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    unit_s: List[float] = dataclasses.field(default_factory=list)   # per step
    elapsed_s: float = 0.0
    asked_s: float = 0.0                # --seconds, before a trace shortens it
    program: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None         # benchmarks.lib.trace.Summary
    compared: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    correct: bool = False


def new_record(cell: Cell, seed: int, devices: List[Any]) -> RunRecord:
    """The record of a run on ``devices``; the published peaks only where
    they are a TPU (a rehearsal on the CPU has none to read against)."""
    from benchmarks.lib import chip
    tpu = devices[0].platform == "tpu"
    return RunRecord(cell=cell, seed=seed, device=chip.describe(devices),
                     peak=chip.peaks(devices[0].device_kind) if tpu else {})


def out_dir(cell_name: str) -> str:
    path = os.path.join(ROOT, "bench_out", cell_name)
    os.makedirs(path, exist_ok=True)
    return path


def write_units(cell_name: str, seed: int, trace: int, header: Tuple[str, ...],
                rows: List[Tuple]) -> str:
    """Every step's (request's) host times, one row each."""
    path = os.path.join(out_dir(cell_name), f"units-seed{seed}-trace{trace}.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def print_setup(setup: Dict[str, float], t_start: float,
                marks: List[Tuple[str, float]]) -> None:
    """Where the set-up time went, on standard error."""
    parts, last = [], t_start
    for label, t in marks:
        parts.append(f"{label} {t - last:.1f}")
        last = t
    print(f"benchmark: set-up {setup['setup_s']:.1f} s = " + " + ".join(parts)
          + f" (JAX compile events and cache reads {setup['compile_s']:.1f} s "
          f"among them)", file=sys.stderr)


def load_reader(name: str, metrics_dir: str = None) -> Tuple[Any, str]:
    """The module that reads the per-layer metric ``name`` and the part it
    is asked for: ``metrics/<name>.py`` and ``""``, or for a metric split by
    suffix (``step_ms_p50.lm``, ``.cnn``) that has no file of its own its
    stem's (``step_ms_p50.py``) and the suffix."""
    metrics_dir = metrics_dir or os.path.join(BENCH_DIR, "metrics")
    path, part = os.path.join(metrics_dir, name + ".py"), ""
    if not os.path.exists(path) and "." in name:
        stem, part = name.rsplit(".", 1)
        path = os.path.join(metrics_dir, stem + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, part


def read_metric(name: str, run: RunRecord, metrics_dir: str = None
                ) -> Optional[float]:
    """The per-layer metric ``name`` by its own reader: ``read(run)``, or of
    a stem that tells its parts apart ``read_part(run, part)``. None where
    it finds nothing to read."""
    module, part = load_reader(name, metrics_dir)
    if part and hasattr(module, "read_part"):
        value = module.read_part(run, part)
    else:
        value = module.read(run)
    return None if value is None else float(value)
