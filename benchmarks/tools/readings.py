"""Read, on the chip and in one process, what a training cell's limits are
set from: over ``--seeds`` seeds the numbers the program gives against the
plain reference (the lower readings), and over the first ``--controls`` of
them the numbers that the control (the reference in fp8) and each planted
fault (half of the batch left out; the exchange between chips left out) give
in the program's place (the upper readings). Training's readings need no
measured window.

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 12 --controls 3

One JSON line per seed on standard output and in
``chiprun_out/readings/<cell>.jsonl``."""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import cell as cells    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--traffic-override", default="{}",
                    help="JSON merged over the traffic file: a second "
                         "witness at another size or precision")
    ap.add_argument("--tag", default="")
    ap.add_argument("--matmul-precision", default="",
                    help="JAX's default matmul precision for the PROGRAM "
                         "(\"highest\": with a float32 dtype in the override, "
                         "the program as a witness in the reference's own "
                         "precision)")
    ap.add_argument("--cpu", action="store_true",
                    help="a witness off the chip, at a size the CPU holds; "
                         "its lines are marked and set no limit")
    args = ap.parse_args(argv)

    from benchmarks.kinds import train
    from benchmarks.lib import chip, compare, lowprec
    cell = cells.load_cell(args.workload)
    cell.traffic.update(json.loads(args.traffic_override))
    import jax
    if not args.cpu:
        chip.place_compile_cache()
    devices = chip.take_chips(cell.chips, require_tpu=not args.cpu)
    family = importlib.import_module(
        "benchmarks.families." + cell.config["family"])
    steps = int(cell.traffic["check_steps"])
    rows = cell.traffic["rows_per_chip"] * cell.chips
    out_dir = os.path.join(cells.ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    name = cell.name + args.tag
    raw = open(os.path.join(out_dir, name + "-raw.jsonl"), "a")
    with open(os.path.join(out_dir, name + ".jsonl"), "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            with jax.default_matmul_precision(
                    args.matmul_precision or None):
                prog = family.TrainProgram(cell.config, cell.traffic, seed,
                                           devices)
                got = train.first_steps(prog, steps)
            prog.release()
            gc.collect()
            t1 = time.perf_counter()
            want = prog.reference(lowprec.F32, steps)
            t2 = time.perf_counter()
            row = {"cell": cell.name, "seed": seed,
                   "platform": devices[0].platform,
                   "override": args.traffic_override,
                   "program_matmul_precision": args.matmul_precision,
                   "program_s": t1 - t0, "reference_s": t2 - t1,
                   "program": compare.training_numbers(got, want),
                   "loss": [got["loss"], want["loss"]]}
            readings = {"seed": seed, "program": got, "reference": want}
            if i < args.controls:
                others = {
                    "fp8_control": prog.reference(lowprec.FP8, steps),
                    "half_batch": prog.reference(lowprec.F32, steps,
                                                 keep_rows=rows // 2)}
                if cell.chips > 1:
                    others["no_exchange"] = prog.reference(
                        lowprec.F32, steps, keep_rows=rows // cell.chips)
                for k, v in others.items():
                    row[k] = compare.training_numbers(v, want)
                readings.update(others)
                row["controls_s"] = time.perf_counter() - t2
            # every leaf's norms, so another number can be tried off the chip
            raw.write(json.dumps(readings) + "\n")
            raw.flush()
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
