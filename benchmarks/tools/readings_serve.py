"""Read, on the chip and in one process, what a serving cell's limit is set
from: over ``--seeds`` seeds a short window at the cell's own load (long
enough to finish the mix's longest requests and to compare as many tokens as
a run does) and the widest gap of a served token below the float32
reference's best (the lower readings); over the first ``--controls`` of them
the same number for the token that the fp8 reference puts first at each
position of the same prompts and tokens (the upper readings).

    python3 benchmarks/tools/readings_serve.py --workload <cell> --seeds 12

One JSON line per seed on standard output and in
``chiprun_out/readings/<cell>.jsonl``."""

from __future__ import annotations

import argparse
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
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--first-seed", type=int, default=2_500_000_017)
    args = ap.parse_args(argv)

    from benchmarks.lib import chip, lowprec
    cell = cells.load_cell(args.workload)
    chip.place_compile_cache()
    devices = chip.take_chips(cell.chips)
    compile_log = chip.CompileLog()
    kind = importlib.import_module("benchmarks.kinds." + cell.traffic["kind"])
    family = importlib.import_module(
        "benchmarks.families." + cell.config["family"])
    out_dir = os.path.join(cells.ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell.name + ".jsonl"), "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            rec = kind.run(cell, seed, args.seconds, 0, devices, t0,
                           compile_log)
            row = {"cell": cell.name, "seed": seed,
                   "wall_s": time.perf_counter() - t0,
                   "requests": rec.attempted, "failed": rec.failed,
                   "compared_tokens": rec.counters["compared_tokens"],
                   "program": {k: v[0] for k, v in rec.compared.items()},
                   "end_to_end": rec.end_to_end}
            if i < args.controls:
                gaps = family.served_token_gaps(
                    cell.config, seed, devices[0], lowprec.F32,
                    rec.counters["served_sample"],
                    int(cell.traffic["check_pad_to"]), against=lowprec.FP8)
                row["fp8_control"] = {
                    "served_logit_gap": float(max(g.max() for g in gaps)),
                    "positions_moved": int(sum((g > 0).sum() for g in gaps))}
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
