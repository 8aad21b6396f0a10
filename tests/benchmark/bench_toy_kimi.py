"""The toy cell of the ``kimi_k2`` family for the CPU rehearsals: the real
harness, the ``serve_closed`` kind, the family's ``ServeProgram``, its
routing and latent-row counters and its layer-by-layer reference, at a width
a test holds (YaRN at a factor of 4, a router of 32 outputs with 12 held)."""

import os

import bench_toy
from benchmarks.lib.cell import Cell, load_json

NAME = "toy_kimi_serve_closed"


def cell() -> Cell:
    e2e = [{"name": "serve_out_tokens_per_s", "unit": "tok/s"},
           {"name": "setup_s", "unit": "s"}]
    return Cell(
        name=NAME, chips=1, config_name="toy_kimi",
        config=load_json(os.path.join(bench_toy.DATA, "toy_kimi.json")),
        traffic_name="toy_serve_closed_kimi",
        traffic=load_json(os.path.join(
            bench_toy.DATA, "toy_serve_closed_kimi.json")),
        end_to_end=e2e, per_layer=[])
