"""The device: where the compile cache lives, which chips a run may use,
their published peaks and what they hold."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

from benchmarks.lib.cell import BENCH_DIR, ROOT

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def place_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at ``<checkout>/.jax_cache``: a fixed path, because the path
    is part of the key. Every program is cached, however quick to compile.
    Call before the first compile."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.environ[CACHE_ENV] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def take_chips(n: int, require_tpu: bool = True) -> List[Any]:
    """The first ``n`` devices, or :class:`NoChip`. Off the TPU only a
    rehearsal (``require_tpu=False``, reachable from Python alone) runs."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(
            f"JAX found platform {devices[0].platform!r} "
            f"({devices[0].device_kind}), not a TPU; the benchmark measures "
            f"only on the chip")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return list(devices[:n])


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmarks/peaks.json (have {sorted(table)}); add a row with "
            f"its source, a default would be a guess")
    return table[device_kind]


def memory_peak_bytes(devices: List[Any]) -> int:
    """Peak bytes held on the fullest chip, as the runtime reports it: the
    larger of ``peak_bytes_in_use`` (live buffers) and ``peak_bytes_reserved``
    (what running executables reserve for their temporaries). On this runtime
    the first leaves the second out: a ResNet-50 step whose compiled
    temporaries are 9.0 GB read 0.81 GB in use and 8.96 GB reserved (my chip
    run, PR 24). Whether the two overlap is not known, so they are not
    added."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


def describe(devices: List[Any]) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": str(d.platform), "kind": str(d.device_kind),
            "count": len(devices)}


class CompileLog:
    """Seconds JAX spent in backend compiles (cache reads included), and how
    many fell after ``mark_window()``."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.setup_s = 0.0
        self.in_window = 0
        self._window = False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_: Any) -> None:
        if event != COMPILE_EVENT:
            return
        if self._window:
            self.in_window += 1
        else:
            self.setup_s += float(seconds)

    def mark_window(self, on: bool) -> None:
        self._window = on
