"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, ``breakdown``;
``window``, the seconds asked for and the seconds measured (a traced run
measures the traffic file's ``trace_seconds``); last in it ``compared``, every number the comparison with the plain reference
read beside its limit. The same numbers end standard error.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name: ``configs/<config>.json`` (its
``family`` names ``families/<family>.py`` and the plain reference),
``traffic/<traffic>.json`` (its ``kind`` names ``kinds/<kind>.py``),
``metrics/<metric>.py``. Without a TPU, or with fewer chips than the cell
asks for, the command exits 1 and prints no result."""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import cell as cells    # noqa: E402


def result_line(rec, trace: int) -> dict:
    from benchmarks.lib import report
    if trace:
        metrics = {}
        for m in rec.cell.per_layer:
            value = report.read_metric(m["name"], rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": rec.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in rec.cell.end_to_end}
    device = dict(rec.device)
    line = {"correct": bool(rec.correct), "attempted": int(rec.attempted),
            "failed": int(rec.failed), "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        line["breakdown"] = rec.trace.breakdown()
    # what was measured, where a traced run's window is shorter than asked
    line["window"] = {"asked_s": rec.asked_s, "ran_s": rec.elapsed_s}
    line["compared"] = rec.compared
    return line


def run_cell(cell, seed: int, seconds: float, trace: int,
             require_tpu: bool = True, t_start: float = None) -> dict:
    """Drive one cell and return the result line. ``require_tpu=False`` is
    the rehearsal switch of the tests: it skips the look for a chip and
    nothing else, and no command-line option reaches it."""
    from benchmarks.lib import chip
    t_start = T_START if t_start is None else t_start
    devices = chip.take_chips(cell.chips, require_tpu=require_tpu)
    if require_tpu:         # a rehearsal leaves the process's JAX as it is
        chip.place_compile_cache()
    compile_log = chip.CompileLog()
    kind = importlib.import_module("benchmarks.kinds." + cell.traffic["kind"])
    rec = kind.run(cell, seed, seconds, trace, devices, t_start, compile_log)
    line = result_line(rec, trace)
    for name, (value, limit) in rec.compared.items():
        print(f"benchmark: compared {name} = {value:.6g} (limit {limit:g})"
              f"{'' if value <= limit else '  <-- over'}", file=sys.stderr)
    print(f"benchmark: correct = {line['correct']}", file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.lib import chip
    cell = cells.load_cell(args.workload)
    try:
        line = run_cell(cell, args.seed, args.seconds, args.trace)
    except chip.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
