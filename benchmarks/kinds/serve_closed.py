"""Traffic kind ``serve_closed``: N closed-loop clients on one replica.

Each client sends its next request when it sees its last one finished, with
no think time. Lengths are stratified: every client's cycle covers the same
quantile midpoints of the prompt-length and output-length distributions in an
order the traffic file fixes; the seed draws the token ids (and the weights),
so a seed never changes the amount of work in a window. Before the clock starts the loop
runs until every client has had one request finished, so the window opens on
full slots at mixed phases. The window runs for at least ``seconds`` and
closes with the scheduling cycle that passes them; the clock stops when that
cycle's tokens have been read back. The rate is every token that came out
inside the window over that time; ``attempted`` counts the requests that
finished in it. The clients never pause, so the replica is at its capacity by
construction and a first token's wait says how the clients' phases fell, not
what a caller under a lighter load would feel: the times to first token go
to the per-request file, their median is a per-layer metric and their 95th
percentile a line on standard error, and no end-to-end metric stands on
them (PERF.md, Open questions: the open-loop cells).
(A window closed on a request's end, counting finished requests' tokens, read
+-0.6 % from which request happened to be last: 83 or 84 of them in 30 s.)"""

from __future__ import annotations

import gc
import importlib
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmarks.lib import chip, compare, lowprec, report, window


# what this kind stores under ``rec.counters`` itself; the family's program
# brings the rest (``program.counters()``)
OWN_COUNTERS = ("requests_per_s", "ttft_s", "batch_occupancy",
                "compared_tokens", "served_sample")


def quantile_lengths(spec: Dict[str, Any], strata: int) -> List[int]:
    """Midpoints of ``strata`` equal-probability slices of the distribution."""
    q = (np.arange(strata) + 0.5) / strata
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if spec["dist"] == "loguniform":
        values = lo * (hi / lo) ** q
    elif spec["dist"] == "uniform":
        values = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(round(v)) for v in values]


class Clients:
    """The generator: client c's i-th request. The SIZES and their order are
    fixed by the traffic file, the same for every seed: client c starts its
    cycle at stratum c and walks the prompt lengths forwards and the output
    lengths at a stride, so at any time the slots hold all strata at mixed
    phases. The seed draws the token ids. (A window holds less than one whole
    cycle of all clients, so an order drawn from the seed changed the work in
    it: +-3 % in tokens/s between seeds, my chip run, PR 24.)"""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int):
        self.prompts = quantile_lengths(traffic["prompt_len"],
                                        traffic["strata"])
        self.outputs = quantile_lengths(traffic["output_len"],
                                        traffic["strata"])
        self.strata = int(traffic["strata"])
        self.vocab = vocab
        self.rng = [np.random.default_rng([seed, c])
                    for c in range(traffic["clients"])]
        self.sent = [0] * traffic["clients"]

    def next(self, client: int) -> Tuple[np.ndarray, int]:
        i = self.sent[client]
        self.sent[client] += 1
        n_prompt = self.prompts[(client + i) % self.strata]
        # 3 is coprime to a power-of-two number of strata: every pairing
        # of a client's cycle is distinct
        n_out = self.outputs[(client // self.strata + 3 * i + client)
                             % self.strata]
        ids = self.rng[client].integers(0, self.vocab, n_prompt)
        return ids.astype(np.int32), n_out


def run(cell, seed: int, seconds: float, trace: int, devices: List[Any],
        t_start: float, compile_log: chip.CompileLog) -> report.RunRecord:
    traffic = cell.traffic
    family = importlib.import_module(
        "benchmarks.families." + cell.config["family"])
    rec = report.new_record(cell, seed, devices)
    spans = window.Spans(annotate=bool(trace))
    marks = [("start, imports, chip", time.perf_counter())]
    prog = family.ServeProgram(cell.config, traffic, seed, devices, spans)
    marks.append(("weights, engine and its programs", time.perf_counter()))
    sched = prog.scheduler
    clients = Clients(traffic, prog.vocab, seed)

    submitted: Dict[int, Tuple[int, float]] = {}    # rid -> (client, t)
    first_seen: Dict[int, float] = {}               # rid -> t of first token
    # req, time submitted, time to first token, time finished, client
    finished: List[Tuple[Any, float, float, float, int]] = []
    waiting: Dict[int, Any] = {}                    # rid -> request in flight
    wanted: Dict[int, int] = {}                     # rid -> tokens asked for
    n_done = 0
    rid = 0

    def submit(client: int) -> None:
        nonlocal rid
        prompt, n_out = clients.next(client)
        req = prog.request(rid, prompt, n_out)
        now = time.perf_counter()
        req.arrival = now
        submitted[rid] = (client, now)
        waiting[rid] = req
        wanted[rid] = n_out
        sched.submit(req)
        rid += 1

    first_token_at: List[Tuple[float, float]] = []  # (when seen, ttft)

    def emitted() -> int:
        """Tokens that have come out so far, of all requests."""
        return (sum(len(f[0].tokens) for f in finished)
                + sum(len(req.tokens) for req in waiting.values()))

    def cycle() -> int:
        """One scheduling cycle; how many requests finished in it."""
        nonlocal n_done
        with spans.span("bench.schedule"):
            sched.step()
        now = time.perf_counter()
        for r, req in waiting.items():
            if r not in first_seen and req.tokens:
                first_seen[r] = now
                first_token_at.append((now, now - submitted[r][1]))
        done = sched.completed[n_done:]
        n_done = len(sched.completed)
        for req in done:
            del waiting[req.rid]
            client, t_sub = submitted.pop(req.rid)
            finished.append((req, t_sub, first_seen.pop(req.rid, now) - t_sub,
                             now, client))
            submit(client)
        return len(done)

    for c in range(traffic["clients"]):
        submit(c)
    while len({f[4] for f in finished}) < traffic["clients"]:
        cycle()                                     # the warm rotation
    marks.append(("warm rotation", time.perf_counter()))
    rec.setup = {"setup_s": time.perf_counter() - t_start,
                 "compile_s": compile_log.setup_s}
    report.print_setup(rec.setup, t_start, marks)

    session = None
    rec.asked_s = seconds
    seconds = window.length(seconds, traffic, trace)
    if trace:
        from benchmarks.lib import trace as tracing
        session = tracing.Session(cell.name, seed)
    stats0 = sched.stats()
    totals0 = window.counter_marks(prog.counters(), OWN_COUNTERS)
    mark = (len(finished), len(prog.decode_s), emitted(), len(first_token_at))
    with window.measured(compile_log, session), spans.span("bench.window"):
        t0 = time.perf_counter()
        while True:
            cycle()
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    stats1 = sched.stats()
    family_counters = prog.counters()
    counted = finished[mark[0]:]
    out_tokens = emitted() - mark[2]
    ttft = [t for _, t in first_token_at[mark[3]:]]
    rec.elapsed_s = elapsed
    rec.compiles_in_window = compile_log.in_window
    rec.attempted = len(counted)
    rec.failed = sum(1 for req, *_ in counted
                     if req.error is not None
                     or len(req.tokens) != wanted[req.rid])
    rec.end_to_end = {
        "serve_out_tokens_per_s": out_tokens / elapsed,
        "setup_s": rec.setup["setup_s"]}
    steps = stats1["decode_steps"] - stats0["decode_steps"]
    rec.unit_s = prog.decode_s[mark[1]:]
    rec.counters = {
        **window.added_since(totals0, family_counters),
        "requests_per_s": len(counted) / elapsed,
        "ttft_s": ttft,
        "batch_occupancy": (
            (stats1["mean_occupancy"] * stats1["decode_steps"]
             - (stats0["mean_occupancy"] or 0.0) * stats0["decode_steps"])
            / steps) if steps else None,
    }
    hlo_texts = prog.hlo_texts() if trace else {}
    rec.program = {"hlo_texts": hlo_texts,
                   "hlo_text": hlo_texts.get(prog.main_program, ""),
                   **prog.facts()}
    rec.device["memory_peak_bytes"] = chip.memory_peak_bytes(devices)

    path = report.write_units(
        cell.name, seed, trace,
        ("rid", "prompt_tokens", "out_tokens", "submit_s", "ttft_s", "end_s"),
        [(req.rid, int(req.prompt.size), len(req.tokens), sub - t0, t, end - t0)
         for req, sub, t, end, _ in counted])
    slow = ", ".join(
        f"request {req.rid} ({int(req.prompt.size)} prompt tokens): "
        f"{t * 1e3:.1f} ms"
        for req, _, t, _, _ in sorted(counted, key=lambda f: -f[2])[:3])
    print(f"benchmark: {len(counted)} requests finished, {len(ttft)} first "
          f"tokens (median {window.median(ttft) * 1e3:.1f} ms, 95th "
          f"percentile {window.percentile(ttft, 95) * 1e3:.1f} ms), "
          f"{out_tokens} tokens in "
          f"{elapsed:.4f} s, {steps} decode steps (median "
          f"{window.median(rec.unit_s) * 1e3:.2f} ms), "
          f"{rec.compiles_in_window} compiles; slowest first tokens: "
          f"{slow}; every request in {path}", file=sys.stderr)

    # the sample: the longest finished request and some drawn from the seed
    rng = np.random.default_rng([seed, 10 ** 6])
    order = sorted(range(len(counted)), key=lambda i: -(
        int(counted[i][0].prompt.size) + len(counted[i][0].tokens)))
    picks = order[:1] + [int(i) for i in rng.permutation(order[1:])[
        :int(traffic["check_requests"]) - 1]]
    served = [(np.asarray(counted[i][0].prompt), list(counted[i][0].tokens))
              for i in picks]
    prog.release()
    del sched, counted, finished, waiting
    gc.collect()
    if trace:
        rec.trace = session.reduce(hlo_texts)
    gaps = prog.reference_gaps(lowprec.F32, served,
                               int(traffic["check_pad_to"]))
    numbers = {"served_logit_gap": float(max(g.max() for g in gaps))}
    rec.counters["compared_tokens"] = int(sum(len(g) for g in gaps))
    rec.counters["served_sample"] = served      # for the control's readings
    rec.correct, rec.compared = compare.judge(numbers, traffic["limits"])
    rec.correct = rec.correct and rec.failed == 0
    return rec
