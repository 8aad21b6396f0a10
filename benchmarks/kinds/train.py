"""Traffic kind ``train``: a compiled training step driven step after step.

Set-up builds the family's ``TrainProgram`` (compiled step + state, weights
and rows from the seed), drives it through its first steps while reading what
the comparison needs, runs a few more untimed steps, and hands the same object
to the window. After the window the program's state is freed and the plain
reference follows the same first steps on the same weights and rows."""

from __future__ import annotations

import gc
import importlib
import math
import sys
import time
from typing import Any, Dict, List

from benchmarks.lib import chip, compare, lowprec, report, window


# what this kind stores under ``rec.counters`` itself; the family's program
# brings the rest (``program.counters()``)
OWN_COUNTERS = ("steps_in_trace",)


def first_steps(prog: Any, steps: int) -> Dict[str, Any]:
    """The program's readings over its first ``steps`` steps, through the
    window's own call (``prog.step``) and feed."""
    prog.snapshot()
    out: Dict[str, Any] = {"loss": []}
    for k in range(steps):
        out["loss"].append(float(prog.step(k)))
        if k == 0:
            out["grad"] = prog.first_gradient_norms()
    out.update(prog.change_norms())
    return out


def run(cell, seed: int, seconds: float, trace: int, devices: List[Any],
        t_start: float, compile_log: chip.CompileLog) -> report.RunRecord:
    traffic = cell.traffic
    family = importlib.import_module(
        "benchmarks.families." + cell.config["family"])
    rec = report.new_record(cell, seed, devices)
    spans = window.Spans(annotate=bool(trace))

    marks = [("start, imports, chip", time.perf_counter())]
    prog = family.TrainProgram(cell.config, traffic, seed, devices)
    marks.append(("weights, rows, step program", time.perf_counter()))
    check_steps = int(traffic["check_steps"])
    got = first_steps(prog, check_steps)
    marks.append(("check steps and their readings", time.perf_counter()))
    k0 = check_steps
    for k in range(k0, k0 + int(traffic["warm_steps"])):
        loss = prog.step(k)
    float(loss)                                 # the barrier: nothing in flight
    marks.append(("warm steps", time.perf_counter()))
    k0 += int(traffic["warm_steps"])
    mem = prog.compiled.memory_analysis()
    hlo_texts = prog.hlo_texts() if trace else {}
    rec.program = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "hlo_texts": hlo_texts,
        "hlo_text": hlo_texts.get(prog.main_program, ""),
        "items_per_step": prog.items_per_step,
        "required_flops_per_step": prog.required_flops_per_step,
        **prog.facts(),
    }
    rec.setup = {"setup_s": time.perf_counter() - t_start,
                 "compile_s": compile_log.setup_s}
    report.print_setup(rec.setup, t_start, marks)

    session = None
    rec.asked_s = seconds
    seconds = window.length(seconds, traffic, trace)
    if trace:
        from benchmarks.lib import trace as tracing
        session = tracing.Session(cell.name, seed)
    losses: List[float] = []
    totals0 = window.counter_marks(prog.counters(), OWN_COUNTERS)
    with window.measured(compile_log, session), spans.span("bench.window"):
        win = window.run_steps(
            lambda k: prog.step(k0 + k),
            lambda loss: losses.append(float(loss)),
            seconds, in_flight=int(traffic["steps_in_flight"]), spans=spans)
    rec.compiles_in_window = compile_log.in_window
    rec.unit_s, rec.elapsed_s = win.step_s, win.elapsed_s
    rec.attempted = win.counted
    rec.counters = {**window.added_since(totals0, prog.counters()),
                    "steps_in_trace": win.counted + win.drained}
    rec.failed = sum(1 for v in losses[:win.counted] if not math.isfinite(v))
    rate = win.counted * prog.items_per_step / win.elapsed_s / len(devices)
    rec.end_to_end = {traffic["rate_metric"]: rate,
                      "setup_s": rec.setup["setup_s"]}
    rec.device["memory_peak_bytes"] = chip.memory_peak_bytes(devices)

    path = report.write_units(
        cell.name, seed, trace, ("step", "end_s", "step_s", "loss"),
        [(i, e, s, l) for i, (e, s, l) in
         enumerate(zip(win.ends_s, win.step_s, losses))])
    slow = ", ".join(f"step {i}: {s * 1e3:.2f} ms"
                     for i, s in window.slowest(win.step_s))
    print(f"benchmark: {win.counted} steps in {win.elapsed_s:.4f} s "
          f"(median {window.median(win.step_s) * 1e3:.2f} ms, "
          f"{win.drained} more in flight, not counted), "
          f"{rec.compiles_in_window} compiles; slowest {slow}; "
          f"every step in {path}", file=sys.stderr)

    # the program's state goes before the reference comes
    prog.release()
    gc.collect()
    if trace:
        rec.trace = session.reduce(hlo_texts)
    want = prog.reference(lowprec.F32, check_steps)
    rec.correct, rec.compared = compare.judge(
        compare.training_numbers(got, want), traffic["limits"])
    rec.correct = rec.correct and rec.failed == 0
    return rec
