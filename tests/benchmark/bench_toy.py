"""Toy cells for the CPU rehearsals: the real harness, kinds, families and
references at a width a test can hold. Kernels run in interpret mode."""

import importlib
import os
import time

from benchmarks.lib.cell import Cell, load_json

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

E2E = {
    "toy_lm_train": [("tokens_per_s_per_chip", "tok/s/chip")],
    "toy_cnn_train": [("images_per_s_per_chip", "img/s/chip")],
    "toy_serve_closed": [("serve_out_tokens_per_s", "tok/s")],
}
CELLS = {
    "lm_train_1": ("toy_lm", "toy_lm_train", 1),
    "lm_train_4": ("toy_lm", "toy_lm_train", 4),
    "cnn_train_1": ("toy_resnet", "toy_cnn_train", 1),
    "serve_closed": ("toy_lm", "toy_serve_closed", 1),
}


def cell(name: str) -> Cell:
    config, traffic, chips = CELLS[name]
    e2e = [{"name": n, "unit": u} for n, u in E2E[traffic]]
    e2e.append({"name": "setup_s", "unit": "s"})
    return Cell(name="toy_" + name, chips=chips, config_name=config,
                config=load_json(os.path.join(DATA, config + ".json")),
                traffic_name=traffic,
                traffic=load_json(os.path.join(DATA, traffic + ".json")),
                end_to_end=e2e, per_layer=[])


def rehearse(name: str, seed: int = 7, seconds: float = 0.3, trace: int = 0):
    """One whole run of a toy cell, the look for a chip skipped."""
    from benchmarks import run
    return run.run_cell(cell(name), seed, seconds, trace, require_tpu=False,
                        t_start=time.perf_counter())


def record(name: str, seed: int = 7, seconds: float = 0.3):
    """The record of one whole untraced run of a toy cell, as the traffic
    kind hands it to the metric readers."""
    from benchmarks.lib import chip
    c = cell(name)
    kind = importlib.import_module("benchmarks.kinds." + c.traffic["kind"])
    devices = chip.take_chips(c.chips, require_tpu=False)
    return kind.run(c, seed, seconds, 0, devices, time.perf_counter(),
                    chip.CompileLog())


def count_calls(monkeypatch, program_cls, method: str, counter: str) -> None:
    """A toy counter of a family's own: ``program_cls.counters()`` gains
    ``counter``, the calls of ``method`` so far, and ``counter + "_at"``, a
    list that grows by one with each call."""
    sound_method = getattr(program_cls, method)
    sound_counters = program_cls.counters

    def counted(self, *args, **kw):
        self.toy_calls = getattr(self, "toy_calls", 0) + 1
        return sound_method(self, *args, **kw)

    def counters(self):
        n = getattr(self, "toy_calls", 0)
        return {**sound_counters(self), counter: n,
                counter + "_at": list(range(n))}

    monkeypatch.setattr(program_cls, method, counted)
    monkeypatch.setattr(program_cls, "counters", counters)
