"""Toy cells for the CPU rehearsals: the real harness, kinds, families and
references at a width a test can hold. Kernels run in interpret mode."""

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
