"""The toy cell of the ``olmo_hybrid`` family for the CPU rehearsals: the
real harness, the ``serve_closed`` kind, the family's ``ServeProgram``, its
slot-state counters and its layer-by-layer reference, at a width a test holds
(state heads of 16 x 64, two a row of 128 lanes)."""

import os

import bench_toy
from benchmarks.lib.cell import Cell, load_json

NAME = "toy_olmo_serve_closed"


def cell() -> Cell:
    e2e = [{"name": "serve_out_tokens_per_s", "unit": "tok/s"},
           {"name": "setup_s", "unit": "s"}]
    return Cell(
        name=NAME, chips=1, config_name="toy_olmo",
        config=load_json(os.path.join(bench_toy.DATA, "toy_olmo.json")),
        traffic_name="toy_serve_closed_olmo",
        traffic=load_json(os.path.join(
            bench_toy.DATA, "toy_serve_closed_olmo.json")),
        end_to_end=e2e, per_layer=[])
