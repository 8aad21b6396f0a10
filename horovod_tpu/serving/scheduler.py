"""Continuous-batching scheduler (iteration-level scheduling, Orca
OSDI '22) over a :class:`~horovod_tpu.serving.engine.ServeEngine`.

The eager coordinator's cycle idiom (ops/coordinator.py: drain the
queue, bin, dispatch, repeat on a deadline) applied to requests instead
of tensors: every engine *step boundary* is a scheduling point —

1. **retire** slots whose request finished (max_new_tokens or EOS);
   their pages return to the free list immediately;
2. **admit** queued requests into free slots while both a slot and the
   worst-case page reservation are available; admission runs the
   request's chunked prefill (bounded by HOROVOD_SERVE_PREFILL_CHUNK,
   so in-flight decodes stall at most one chunk) and records TTFT at
   its first generated token;
3. **decode** one batched step across all occupied slots — enqueued
   BEFORE the step before it has been read: tokens go from step to step
   on the device, and the host reads a step's tokens while the device
   runs the next one (below).

When every slot is idle the scheduler polls the queue with the
HOROVOD_SERVE_QUEUE_DEADLINE timeout (the cycle-time analogue); while
anything is decoding, admission happens at every step with no wait.

**One step late.** The programs are greedy and a step's input is the
last step's output, so the host needs no token's VALUE to schedule the
next step: cycle c enqueues step c, then waits for step c-1 and appends
its tokens to the requests that were in it. A request therefore ends by
what has been DISPATCHED for it: once its dispatched tokens reach
``max_new_tokens`` it is masked out of the next step, so no request
decodes past its cap. An ``eos_token`` is seen when its step is read,
one step late: the step dispatched meanwhile wrote inside the request's
own reservation, its token is discarded, and the request's tokens end
with the EOS. A prompt's first token is read with the decode step queued
behind its last chunk. ``ttft`` / ``tpot`` are stamped when the host
sees a token. A slot and its pages are released when the request's last
token has been read; a step still queued over them runs before any later
program, and the next dispatch shows the scratch page in that row.
Speculation needs the values to accept a draft and reads every step
before it goes on.

Per-request output is bitwise-identical to the same request run alone:
prefill is per-request by construction, and the batched decode computes
each slot's row from its own pages only — slot index and co-tenants
change which HBM pages hold the bytes, never the values a row reduces
over (CI-pinned in tests/test_serving.py).

``mode="static"`` is the measured baseline: classic static batching
(admit only when ALL slots are free, run the whole batch to completion,
repeat) — `bench.py serve` must show continuous strictly beating it.

Speculative decoding (hvdspec): with ``HOROVOD_SERVE_DRAFT`` set the
decode point becomes draft-then-verify — a drafter proposes K tokens
per slot (host-side :class:`NGramDrafter`, or the engine's
truncated-layer draft model) and ONE batched verify step scores all
K+1 positions per slot. Acceptance is the greedy accept-prefix rule:
draft i is accepted while it equals the token the verify step itself
emitted one position earlier, so the committed sequence is bitwise the
sequential-decode sequence — between 1 and K+1 tokens per step.
Rejected suffixes roll the slot length back (``engine.rollback``),
generalizing the retire logic: EOS and the generation cap truncate the
accepted run exactly where sequential decode would have stopped.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from horovod_tpu import tracing as trace
from horovod_tpu.config import knobs
from horovod_tpu.serving.engine import ServeEngine
from horovod_tpu.utils.logging import get_logger

logger = get_logger("horovod_tpu.serving")


@dataclasses.dataclass
class Request:
    """One generation request. ``prompt`` is a 1-D int32 token array;
    results accumulate in place as the scheduler advances it.
    ``arrival`` is an open-loop timestamp offset for ``run(traffic)``;
    left None, ``submit()`` stamps it — so TTFT always includes the real
    queue wait."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int = 0                 # 0 = HOROVOD_SERVE_MAX_NEW_TOKENS
    eos_token: Optional[int] = None
    arrival: Optional[float] = None
    # -- filled by the scheduler --
    admitted_at: Optional[float] = None     # left the queue for a slot
    cached_tokens: int = 0                  # prompt tokens the prefix index held
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft: Optional[float] = None            # arrival -> first token
    tpot: List[float] = dataclasses.field(default_factory=list)
    finished_at: Optional[float] = None
    slot: Optional[int] = None
    error: Optional[str] = None             # rejected requests carry why
    _last_token_t: float = 0.0
    _prefill_pos: int = 0                   # next prompt offset to prefill
    _dispatched: int = 0                    # tokens made or queued to be made
    _first: Any = None                      # the first token, while unread

    @property
    def done(self) -> bool:
        return self.finished_at is not None

    @property
    def ended(self) -> bool:
        """The tokens the host has seen complete the request: its cap is
        met, or the last of them is its EOS."""
        return (len(self.tokens) >= self.max_new_tokens
                or (self.eos_token is not None and bool(self.tokens)
                    and self.tokens[-1] == self.eos_token))


def _span_attrs() -> Optional[Dict[str, Any]]:
    """A dict for a span's counts, filled before the span closes — or
    None while the recorder is off, so the off path builds nothing."""
    return {} if trace.enabled() else None


def _metrics():
    from horovod_tpu import metrics as M
    return {
        "requests": M.counter(
            "hvd_serve_requests_total",
            "Serving requests by lifecycle edge",
            labelnames=("event",)),
        "tokens": M.counter(
            "hvd_serve_tokens_total",
            "Tokens through the serving engine",
            labelnames=("kind",)),
        "queue": M.gauge(
            "hvd_serve_queue_depth",
            "Requests admitted to the scheduler but not yet in a "
            "decode slot"),
        "occupancy": M.gauge(
            "hvd_serve_batch_occupancy",
            "Occupied fraction of the decode batch slots",
            aggregation="leader"),
        "ttft": M.histogram(
            "hvd_serve_ttft_seconds",
            "Time to first token (arrival -> first generated token, "
            "queue wait included)", buckets=M.LATENCY_BUCKETS),
        "tpot": M.histogram(
            "hvd_serve_tpot_seconds",
            "Time per output token during decode (inter-token "
            "interval)", buckets=M.LATENCY_BUCKETS),
    }


def _record_request(req: "Request") -> None:
    """A retired request's life as spans, from the timestamps it holds:
    ``serve.request`` (arrival -> finish) and under it
    ``serve.request.queued`` (arrival -> admitted),
    ``serve.request.prefill`` (admitted -> first token) and
    ``serve.request.decode`` (first token -> finish). All carry the same
    attrs, ``rid`` among them; a rejected request has the first two
    stamps only, so it gets the parent alone. Ring only: a span that is
    already over cannot be put on the profiler's clock."""
    if req.arrival is None or req.finished_at is None:
        return
    attrs = {"rid": req.rid, "prompt_tokens": int(req.prompt.size),
             "cached_tokens": req.cached_tokens,
             "output_tokens": len(req.tokens), "slot": req.slot}
    if req.error is not None:
        attrs["error"] = req.error
    first = req.arrival + req.ttft if req.ttft is not None else None
    parent = trace.record_interval(
        "serve.request", trace.CAT_SERVE, req.arrival, req.finished_at,
        attrs=attrs)
    for name, t0, t1 in (("queued", req.arrival, req.admitted_at),
                         ("prefill", req.admitted_at, first),
                         ("decode", first, req.finished_at)):
        if t0 is not None and t1 is not None:
            trace.record_interval(
                "serve.request." + name, trace.CAT_SERVE, t0, t1,
                attrs=attrs, parent_id=parent)


class NGramDrafter:
    """Host-side n-gram drafter: propose the K tokens that followed the
    most recent earlier occurrence of the request's current n-token
    suffix (prompt + generated history), falling back to shorter
    suffixes down to a single token. Free (no device work, no compile)
    and surprisingly effective on self-repeating generations — the
    verify step makes a wrong guess cost nothing but its slot-row in
    the already-fixed-shape verify batch."""

    def __init__(self, n: int = 3):
        self.n = max(int(n), 1)

    def propose(self, history: Sequence[int], k: int) -> List[int]:
        h = [int(t) for t in history]
        out: List[int] = []
        for n in range(min(self.n, max(len(h) - 1, 0)), 0, -1):
            tail = h[-n:]
            for i in range(len(h) - n - 1, -1, -1):
                if h[i:i + n] == tail:
                    out = h[i + n:i + n + k]
                    break
            if out:
                break
        last = h[-1] if h else 0
        while len(out) < k:
            out.append(out[-1] if out else last)
        return out[:k]


class ServeScheduler:
    """Single-threaded scheduling loop over one engine (the serving
    analogue of the coordinator's cycle thread; bench and tests drive
    :meth:`run` directly, a server front-end would feed
    :meth:`submit` from its transport threads via a lock)."""

    def __init__(self, engine: ServeEngine, mode: str = "continuous",
                 queue_deadline: Optional[float] = None):
        if mode not in ("continuous", "static"):
            raise ValueError(f"unknown scheduler mode {mode!r}")
        self.engine = engine
        self.mode = mode
        self.queue_deadline = float(
            queue_deadline if queue_deadline is not None
            else knobs.get("HOROVOD_SERVE_QUEUE_DEADLINE"))
        self.default_max_new = int(
            knobs.get("HOROVOD_SERVE_MAX_NEW_TOKENS"))
        self.queue: Deque[Request] = deque()
        self.prefilling: Dict[int, Request] = {}    # slot -> request
        self.active: Dict[int, Request] = {}        # slot -> request
        self.completed: List[Request] = []
        # the decode step the device owes the host: (its next tokens,
        # unread; the (slot, request) pairs in it; the requests whose
        # prompt's last chunk ran just before it)
        self._in_flight: Optional[Tuple[Any, List[Tuple[int, Request]],
                                        List[Request]]] = None
        # requests whose last chunk ran since the last step was enqueued
        self._firsts: List[Request] = []
        self._m = _metrics()
        self._decode_steps = 0
        self._cycles = 0
        self._occ_sum = 0.0
        self.queue_peak = 0
        # hvdspec tallies (prefix-hit-rate / acceptance-rate sweeps)
        self._spec = engine.spec_k > 0
        self._ngram = (NGramDrafter(engine.draft_n)
                       if engine.draft_mode == "ngram" else None)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.prompt_tokens = 0
        self.cached_tokens = 0
        _register_scheduler(self)

    # -- intake --------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.max_new_tokens <= 0:
            req.max_new_tokens = self.default_max_new
        if req.arrival is None:
            req.arrival = time.perf_counter()
        self.queue.append(req)
        self.queue_peak = max(self.queue_peak, len(self.queue))
        self._m["requests"].labels(event="submitted").inc()
        self._m["queue"].set(len(self.queue))

    # -- scheduling points ---------------------------------------------------
    def _retire(self, now: float) -> None:
        attrs = _span_attrs()
        with trace.span("serve.retire", cat=trace.CAT_SERVE, attrs=attrs):
            retired = 0
            for slot, req in list(self.active.items()):
                if req._first is None and req.ended:
                    req.finished_at = now
                    self.engine.release(slot)   # eviction-on-finish
                    del self.active[slot]
                    self.completed.append(req)
                    self._m["requests"].labels(event="completed").inc()
                    retired += 1
                    if attrs is not None:
                        _record_request(req)
            if attrs is not None:
                attrs["retired"] = retired

    def _admit(self, now: float) -> None:
        if self.mode == "static" and (self.active or self.prefilling):
            return                  # static baseline: whole-batch cycles
        attrs = _span_attrs()
        with trace.span("serve.admit", cat=trace.CAT_SERVE, attrs=attrs):
            admitted, rejected = self._admit_queue(now)
            if attrs is not None:
                attrs.update(admitted=admitted, rejected=rejected,
                             queued=len(self.queue))

    def _admit_queue(self, now: float) -> Tuple[int, int]:
        """Admit from the head of the queue while a slot and the pages
        for the worst case are free; returns (admitted, rejected)."""
        admitted = rejected = 0
        while self.queue:
            req = self.queue[0]
            reject = None
            if int(req.prompt.size) > self.engine.max_seq:
                # over-ceiling prompt: never admissible (prefill would
                # raise the same ceiling)
                reject = (
                    f"prompt of {req.prompt.size} tokens exceeds the "
                    f"serving context ceiling {self.engine.max_seq} "
                    f"({self.engine.ceiling_hint})")
            else:
                # clamp generation to the context ceiling: decoding past
                # the last reserved page would corrupt the request's own
                # cache
                req.max_new_tokens = min(
                    int(req.max_new_tokens),
                    max(self.engine.max_seq - int(req.prompt.size), 0))
            worst = int(req.prompt.size) + int(req.max_new_tokens)
            pool = self.engine.pool
            if reject is None and pool.pages_for(worst) > pool.n_pages:
                # bigger than the WHOLE pool: no amount of retiring can
                # ever free enough pages — waiting would head-of-line
                # block the queue forever (and spin run())
                reject = (
                    f"request needs {pool.pages_for(worst)} KV pages "
                    f"for its worst case of {worst} tokens but the pool "
                    f"holds only {pool.n_pages} "
                    f"(raise HOROVOD_SERVE_PAGES or lower the request's "
                    f"max_new_tokens)")
            if reject is not None:
                self.queue.popleft()
                req.error = reject
                req.finished_at = now
                self.completed.append(req)
                self._m["requests"].labels(event="rejected").inc()
                self._m["queue"].set(len(self.queue))
                rejected += 1
                if trace.enabled():
                    _record_request(req)
                continue
            slot = self.engine.reserve(worst, prompt=req.prompt)
            if slot is None:
                break               # no slot / pages: wait for a finish
            self.queue.popleft()
            self._m["queue"].set(len(self.queue))
            req.slot = slot
            req.admitted_at = now
            # shared-prefix reuse: tokens the prefix index already
            # covers are skipped — prefill starts at the divergence
            req._prefill_pos = int(self.engine.slot_skip[slot])
            req.cached_tokens = req._prefill_pos
            self.prompt_tokens += int(req.prompt.size)
            self.cached_tokens += req._prefill_pos
            self.prefilling[slot] = req
            self._m["requests"].labels(event="admitted").inc()
            admitted += 1
        return admitted, rejected

    def _prefill_cycle(self) -> None:
        """Advance every admitted-but-unprefilled request by exactly ONE
        chunk — the chunked-prefill interleave: a decode step runs
        between consecutive chunks, so in-flight TPOT stalls at most one
        chunk at a time, never a whole long prompt."""
        if not self.prefilling:
            return
        attrs = _span_attrs()
        with trace.span("serve.prefill", cat=trace.CAT_SERVE, attrs=attrs):
            chunks = prefilled = 0
            for slot, req in list(self.prefilling.items()):
                old = req._prefill_pos
                pos, first = self.engine.prefill_chunk(slot, req.prompt,
                                                       old)
                req._prefill_pos = pos
                chunks += 1
                prefilled += pos - old
                self._m["tokens"].labels(kind="prefill").inc(pos - old)
                if first is None:
                    continue
                del self.prefilling[slot]
                self.active[slot] = req
                req._first, req._dispatched = first, 1
                if self._spec:
                    self._see_first(req)    # a draft starts from its value
                else:
                    self._firsts.append(req)
            if attrs is not None:
                attrs.update(chunks=chunks, prompt_tokens=prefilled)

    def _see_first(self, req: Request) -> None:
        """The host reads a prompt's first token (waiting for the last
        chunk where nothing has yet): the request's first, ``ttft``."""
        req.tokens.append(int(req._first))
        req._first = None
        t = time.perf_counter()
        req.ttft = t - req.arrival if req.arrival is not None else 0.0
        req._last_token_t = t
        self._m["ttft"].observe(max(req.ttft, 0.0))
        self._m["tokens"].labels(kind="decode").inc()

    def _see(self) -> int:
        """Take what the device owed the host — the decode step before
        the one just enqueued, read by now — to the requests that were in
        it; how many tokens that appended. A request the host has
        meanwhile seen end (its EOS came out of an earlier step) gets
        nothing: the token is discarded."""
        if self._in_flight is None:
            return 0
        tokens, step, firsts = self._in_flight
        self._in_flight = None
        attrs = _span_attrs()
        with trace.span("serve.see", cat=trace.CAT_SERVE, attrs=attrs):
            for req in firsts:
                self._see_first(req)
            seen = 0
            if tokens is not None:
                nxt = np.asarray(tokens)
                t = time.perf_counter()
                for slot, req in step:
                    if req.ended:
                        continue
                    dt = t - req._last_token_t
                    req.tokens.append(int(nxt[slot]))
                    req.tpot.append(dt)
                    req._last_token_t = t
                    self._m["tpot"].observe(dt)
                    self._m["tokens"].labels(kind="decode").inc()
                    seen += 1
            if attrs is not None:
                attrs.update(tokens=seen + len(firsts), firsts=len(firsts))
        return seen

    def _decode(self) -> None:
        if not self.active and self._in_flight is None:
            return
        attrs = {"active": len(self.active)} if trace.enabled() else None
        with trace.span("serve.decode", cat=trace.CAT_SERVE, attrs=attrs):
            if self._spec:
                self._decode_spec(attrs)
            else:
                self._decode_plain(attrs)

    def _decode_plain(self, attrs: Optional[Dict[str, Any]]) -> None:
        """Enqueue this cycle's step, then take the one before it. What
        is in a step is decided by counts alone: every active request
        with tokens left to dispatch, unless the host has seen its EOS."""
        eng = self.engine
        step = [(slot, req) for slot, req in self.active.items()
                if req._dispatched < req.max_new_tokens and not req.ended]
        firsts, self._firsts = self._firsts, []
        tokens = None
        if step:
            active = np.zeros((eng.slots,), bool)
            active[[slot for slot, _ in step]] = True
            tokens = eng.decode_step(None, active=active)
            for _, req in step:
                req._dispatched += 1
            self._decode_steps += 1
            occ = eng.occupancy()
            self._occ_sum += occ
            self._m["occupancy"].set(occ)
        else:
            eng.drain("idle")       # nothing to queue behind it
        seen = self._see()
        if step or firsts:
            self._in_flight = (tokens, step, firsts)
        if attrs is not None:
            attrs["tokens"] = seen

    def _decode_spec(self, attrs: Optional[Dict[str, Any]]) -> None:
        """Draft-then-verify decode point. Accept-prefix per slot:
        draft i is confirmed while it equals the verify step's own
        emission one position back, so the appended run is bitwise the
        sequential greedy sequence; EOS and the generation cap truncate
        it exactly where sequential decode would stop, and the
        rejected suffix rolls the slot length back."""
        eng = self.engine
        k = eng.spec_k
        tokens = np.zeros((eng.slots,), np.int32)
        active = np.zeros((eng.slots,), bool)
        for slot, req in self.active.items():
            tokens[slot] = req.tokens[-1]
            active[slot] = True
        if self._ngram is not None:
            drafts = np.zeros((eng.slots, k), np.int32)
            for slot, req in self.active.items():
                hist = list(np.asarray(req.prompt).reshape(-1))
                hist += req.tokens
                drafts[slot] = self._ngram.propose(hist, k)
        else:
            drafts = eng.propose_drafts(tokens, active)
        out = eng.spec_step(tokens, drafts, active=active)  # [S, K+1]
        t = time.perf_counter()
        self._decode_steps += 1
        occ = eng.occupancy()
        self._occ_sum += occ
        self._m["occupancy"].set(occ)
        appended = accepted_drafts = 0
        for slot, req in self.active.items():
            g = 0
            while g < k and int(drafts[slot, g]) == int(out[slot, g]):
                g += 1
            accepted = [int(x) for x in out[slot, :g + 1]]
            self.spec_proposed += k
            self.spec_accepted += g
            room = req.max_new_tokens - len(req.tokens)
            accepted = accepted[:max(room, 1)]
            if req.eos_token is not None and req.eos_token in accepted:
                accepted = accepted[:accepted.index(req.eos_token) + 1]
            n_new = len(accepted)
            eng.rollback(slot, (k + 1) - n_new)
            dt = t - req._last_token_t
            req.tokens.extend(accepted)
            req.tpot.extend([dt / n_new] * n_new)
            req._last_token_t = t
            self._m["tpot"].observe(dt / n_new)
            self._m["tokens"].labels(kind="decode").inc(n_new)
            appended += n_new
            accepted_drafts += g
        if attrs is not None:
            attrs.update(tokens=appended, proposed=k * len(self.active),
                         accepted=accepted_drafts)

    def step(self, now: Optional[float] = None) -> None:
        """One scheduling cycle: retire -> admit -> one prefill chunk
        per admitted request -> one decode step. The retire between
        prefill and decode matters: a request whose cap (or EOS) is
        already met by its PREFILL token must not decode one token past
        it."""
        now = time.perf_counter() if now is None else now
        self._cycles += 1
        attrs = ({"cycle": self._cycles, "queued": len(self.queue),
                  "prefilling": len(self.prefilling),
                  "active": len(self.active)}
                 if trace.enabled() else None)
        with trace.span("serve.cycle", cat=trace.CAT_SERVE, attrs=attrs):
            if attrs is not None:
                cpu0, gc0 = time.thread_time(), trace.gc_us()
            self._retire(now)
            self._admit(now)
            self._prefill_cycle()
            self._retire(time.perf_counter())
            self._decode()
            self._retire(time.perf_counter())
            if attrs is not None:
                # wall less CPU less the waits beneath: the time this
                # thread stood off its core or behind the interpreter lock
                attrs["cpu_ms"] = (time.thread_time() - cpu0) * 1e3
                attrs["gc_ms"] = (trace.gc_us() - gc0) * 1e-3

    def run(self, traffic=None) -> List[Request]:
        """Drive cycles until ``traffic`` is exhausted and every request
        completed. ``traffic`` is an optional iterable of Requests whose
        ``arrival`` timestamps are offsets from loop start (open-loop:
        arrivals do not wait for capacity — the bench.py serve Poisson
        pattern)."""
        t0 = time.perf_counter()
        pending = deque(sorted(traffic or [],
                               key=lambda r: r.arrival or 0.0))
        for r in pending:
            r.arrival = t0 + (r.arrival or 0.0)  # offsets -> wall clock
        while pending or self.active or self.prefilling or self.queue:
            now = time.perf_counter()
            while pending and pending[0].arrival <= now:
                self.submit(pending.popleft())
            if not self.active and not self.prefilling and not self.queue:
                # every slot idle: the queue-deadline poll (cycle time)
                wait = min(pending[0].arrival - now,
                           max(self.queue_deadline, 1e-4))
                if wait > 0:
                    time.sleep(wait)
                continue
            self.step(now)
        # every request has ended; a step dispatched before the host saw
        # the last EOS may still be queued, its tokens for nobody
        self.engine.drain("idle")
        self._see()
        self._m["occupancy"].set(self.engine.occupancy())
        return self.completed

    # -- reporting -----------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        done = self.completed
        gen = sum(len(r.tokens) for r in done)
        return {
            "mode": self.mode,
            "queue_depth": len(self.queue),
            "active": len(self.active),
            "prefilling": len(self.prefilling),
            "completed": len(done),
            "generated_tokens": gen,
            "queue_peak": self.queue_peak,
            "decode_steps": self._decode_steps,
            "mean_occupancy": (round(self._occ_sum / self._decode_steps,
                                     4) if self._decode_steps else None),
            "prefix": ({
                "prompt_tokens": self.prompt_tokens,
                "cached_tokens": self.cached_tokens,
                "hit_rate": (round(self.cached_tokens
                                   / self.prompt_tokens, 4)
                             if self.prompt_tokens else None),
            } if self.engine.prefix_cache else None),
            "spec": ({
                "draft": self.engine.draft_spec,
                "k": self.engine.spec_k,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance_rate": (round(self.spec_accepted
                                          / self.spec_proposed, 4)
                                    if self.spec_proposed else None),
            } if self._spec else None),
        }


# ---------------------------------------------------------------------------
# module registry + the /healthz `serving` block payload
# ---------------------------------------------------------------------------

_active_scheduler: Optional[ServeScheduler] = None


def _register_scheduler(s: ServeScheduler) -> None:
    global _active_scheduler
    _active_scheduler = s


def active_scheduler() -> Optional[ServeScheduler]:
    return _active_scheduler


def reset_for_tests() -> None:
    global _active_scheduler
    _active_scheduler = None
