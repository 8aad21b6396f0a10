"""The reader of ``mla_decode_ms_per_step``, the paged latent decode kernel's
device time a decode step, in the two latent cells: nothing to read where the
program has no such kernel (a tree whose decode gathers the block tables),
and its example's number worked by hand."""

import os

import pytest

from benchmarks.lib import cell as cells, report
from test_bench_spec import _made_up_run

NAME = "mla_decode_ms_per_step"
CELLS = ("longcat_flash_omni_serve_c64", "kimi_k2_7_code_serve_c32_p12k")


@pytest.mark.parametrize("cell", CELLS)
def test_the_reader_reads_the_kernel_by_name(cell):
    rec = _made_up_run(cell)
    assert NAME in {m["name"] for m in rec.cell.per_layer}
    assert report.read_metric(NAME, rec) is None
    report.load_reader(NAME, os.path.join(cells.BENCH_DIR, "metrics"))[
        0].example(rec)
    # 12 ms of the kernel over the made-up window's 4 decode steps
    assert report.read_metric(NAME, rec) == pytest.approx(3.0)
    rec.trace = None
    assert report.read_metric(NAME, rec) is None


def test_only_the_latent_cells_report_it():
    spec = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry, = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == list(CELLS)
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels", "serve_out_tokens_per_s", "device_trace")
