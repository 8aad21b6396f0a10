"""Spans inside the serve loop (docs/tracing.md, docs/serving.md): one
``serve.cycle`` per scheduling cycle with its phases as children, the
engine's dispatch / wait boundary inside ``serve.decode`` (a step is
enqueued, then the step BEFORE it is waited for), a retired
request's life as four spans, identical tokens with the recorder on and
off, the spans on a profiler trace's host plane with the recorder off,
and the names of the engine's device programs and of the train step's
scopes. CPU, toy widths."""

import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu import tracing as trace
from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import Request, ServeEngine, ServeScheduler


@pytest.fixture(scope="module", autouse=True)
def _shared_store(tmp_path_factory):
    """One artifact store for the module, as tests/test_serving.py: the
    first engine compiles and publishes, the later ones load."""
    from horovod_tpu.store import artifact_store
    old = os.environ.get("HOROVOD_ARTIFACT_STORE")
    os.environ["HOROVOD_ARTIFACT_STORE"] = str(
        tmp_path_factory.mktemp("serving-tracing-store"))
    artifact_store.reset_for_tests()
    yield
    if old is None:
        os.environ.pop("HOROVOD_ARTIFACT_STORE", None)
    else:
        os.environ["HOROVOD_ARTIFACT_STORE"] = old
    artifact_store.reset_for_tests()


@pytest.fixture(autouse=True)
def _fresh_recorder():
    trace.reset()
    yield
    trace.reset()


def _cfg(**kw):
    base = dict(vocab_size=256, d_model=64, n_heads=4, head_dim=16,
                n_layers=2, d_ff=128, max_seq=256, dtype=jnp.float32,
                dp_axis=None, remat=False)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _scheduler(**kw):
    cfg = _cfg()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    kw.setdefault("slots", 4)
    kw.setdefault("page", 16)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prefill_chunk", 32)
    return ServeScheduler(ServeEngine(cfg, params, mesh=None, **kw))


def _requests(n=3, seed=0):
    rng = np.random.default_rng(seed)
    # 40 prompt tokens: two prefill chunks of the 32-token bucket
    return [Request(rid=i, prompt=rng.integers(0, 256, 40 + 7 * i)
                    .astype(np.int32), max_new_tokens=4 + i)
            for i in range(n)]


def _run(sched, requests, cycles=64):
    for r in requests:
        sched.submit(r)
    for _ in range(cycles):
        if len(sched.completed) == len(requests):
            break
        sched.step()
    assert len(sched.completed) == len(requests)
    return {r.rid: list(r.tokens) for r in sched.completed}


def _by_name(rows):
    out = {}
    for r in rows:
        out.setdefault(r["name"], []).append(r)
    return out


def _inside(child, parent, slack_us=0.5):
    return (child["ts_us"] >= parent["ts_us"] - slack_us
            and child["ts_us"] + child["dur_us"]
            <= parent["ts_us"] + parent["dur_us"] + slack_us)


def test_one_cycle_is_one_span_with_its_phases_as_children():
    sched = _scheduler()
    for r in _requests(2):
        sched.submit(r)
    sched.step()                    # admits both, one prefill chunk each
    sched.step()                    # last chunks -> first tokens, a decode
    trace.enable(buffer_spans=256)
    sched.step()
    trace.disable()
    rows = _by_name(trace.snapshot())
    (cycle,) = rows["serve.cycle"]
    assert cycle["parent_id"] == 0 and cycle["cat"] == "serve"
    counts = {k: v for k, v in cycle["attrs"].items()
              if k not in ("cpu_ms", "gc_ms")}
    assert counts == {"cycle": 3, "queued": 0, "prefilling": 0, "active": 2}
    children = [r for rs in rows.values() for r in rs
                if r["parent_id"] == cycle["span_id"]]
    assert {c["name"] for c in children} == {
        "serve.retire", "serve.admit", "serve.decode"}
    assert len(rows["serve.retire"]) == 3       # one for each _retire call
    assert all(_inside(c, cycle) for c in children)
    (decode,) = rows["serve.decode"]
    assert decode["attrs"] == {"active": 2, "tokens": 2}
    (dispatch,) = rows["engine.decode.dispatch"]
    (wait,) = rows["engine.decode.wait"]
    (see,) = rows["serve.see"]
    for inner in (dispatch, wait, see):
        assert inner["parent_id"] == decode["span_id"]
        assert _inside(inner, decode)
    # the two prompts' first tokens (their last chunks ran a cycle ago) and
    # the two tokens of the step behind them
    assert see["attrs"] == {"tokens": 4, "firsts": 2}
    assert wait["ts_us"] + wait["dur_us"] <= see["ts_us"] + 0.5
    assert dispatch["attrs"] == {"active": 2}
    # this cycle's step is enqueued before anything is waited for, and it
    # was enqueued with the step before it unread: the wait is for THAT
    # step, whose two tokens this cycle shows the requests
    assert dispatch["ts_us"] + dispatch["dur_us"] <= wait["ts_us"] + 0.5
    (ahead,) = rows["engine.decode.ahead"]
    assert ahead["parent_id"] == dispatch["span_id"]
    assert _inside(ahead, dispatch)
    counts = sched.engine.stats()["decode"]
    assert counts == {"steps": 2, "dispatched_ahead": 1, "drained": {}}
    assert rows["serve.admit"][0]["attrs"] == {
        "admitted": 0, "rejected": 0, "queued": 0}


def test_prefill_spans_name_the_chunk_and_nothing_waits_on_the_last():
    sched = _scheduler()
    trace.enable(buffer_spans=256)
    (req,) = _requests(1)
    sched.submit(req)
    sched.step()
    sched.step()
    trace.disable()
    rows = _by_name(trace.snapshot())
    cycles = rows["serve.cycle"]
    assert [c["attrs"]["cycle"] for c in cycles] == [1, 2]
    first, last = rows["serve.prefill"]
    assert first["parent_id"] == cycles[0]["span_id"]
    assert first["attrs"] == {"chunks": 1, "prompt_tokens": 32}
    assert last["attrs"] == {"chunks": 1, "prompt_tokens": 8}
    d0, d1 = rows["engine.prefill.dispatch"]
    assert d0["parent_id"] == first["span_id"]
    assert d0["attrs"] == {"slot": 0, "start": 0, "tokens": 32, "bucket": 32}
    assert d1["attrs"] == {"slot": 0, "start": 32, "tokens": 8, "bucket": 32}
    # the last chunk's token goes to the decode step on the device: a
    # decode step follows the chunk in the same cycle and nothing waits
    # for the chunk
    assert "engine.prefill.wait" not in rows
    (decode,) = rows["serve.decode"]
    assert decode["parent_id"] == cycles[1]["span_id"]
    (dispatch,) = rows["engine.decode.dispatch"]
    assert dispatch["parent_id"] == decode["span_id"]
    assert dispatch["ts_us"] >= d1["ts_us"] + d1["dur_us"] - 0.5
    # the first step of a run has no step before it: nothing to wait for
    assert "engine.decode.wait" not in rows
    assert "engine.decode.ahead" not in rows
    assert rows["serve.admit"][0]["attrs"] == {
        "admitted": 1, "rejected": 0, "queued": 0}


def test_the_direct_api_waits_where_it_did():
    """``engine.prefill`` and ``decode_step(<NumPy tokens>)`` hand back
    values, so each waits for its own program: the prefill's wait is the
    last chunk's readback, the decode's follows its own dispatch, and
    every such step counts as drained."""
    engine = _scheduler().engine
    trace.enable(buffer_spans=256)
    (req,) = _requests(1)
    slot = engine.reserve(int(req.prompt.size) + 4)
    tokens = np.zeros((engine.slots,), np.int32)
    tokens[slot] = engine.prefill(slot, req.prompt)
    tokens[slot] = engine.decode_step(tokens)[slot]
    engine.decode_step(tokens)
    trace.disable()
    rows = _by_name(trace.snapshot())
    (wait,) = rows["engine.prefill.wait"]
    assert wait["attrs"] == {"slot": slot}
    assert wait["ts_us"] >= max(
        d["ts_us"] + d["dur_us"] for d in rows["engine.prefill.dispatch"]
    ) - 0.5
    for dispatch, wait in zip(rows["engine.decode.dispatch"],
                              rows["engine.decode.wait"], strict=True):
        assert dispatch["ts_us"] + dispatch["dur_us"] <= wait["ts_us"] + 0.5
    assert "engine.decode.ahead" not in rows
    assert engine.stats()["decode"] == {
        "steps": 2, "dispatched_ahead": 0, "drained": {"direct": 2}}


def test_a_finished_request_is_four_spans_that_add_up():
    sched = _scheduler()
    trace.enable(buffer_spans=4096)
    requests = _requests(3)
    _run(sched, requests)
    rows = _by_name(trace.snapshot())
    assert len(rows["serve.request"]) == 3
    for req in requests:
        (whole,) = [r for r in rows["serve.request"]
                    if r["attrs"]["rid"] == req.rid]
        assert whole["parent_id"] == 0
        assert whole["attrs"] == {
            "rid": req.rid, "prompt_tokens": int(req.prompt.size),
            "cached_tokens": 0, "output_tokens": len(req.tokens),
            "slot": req.slot}
        parts = [rows["serve.request." + p] for p in
                 ("queued", "prefill", "decode")]
        parts = [[r for r in rs if r["attrs"]["rid"] == req.rid]
                 for rs in parts]
        assert [len(p) for p in parts] == [1, 1, 1]
        queued, prefill, decode = (p[0] for p in parts)
        for part in (queued, prefill, decode):
            assert part["parent_id"] == whole["span_id"]
            assert part["attrs"] == whole["attrs"]
        # arrival -> admitted -> first token -> finish, end to end
        assert queued["ts_us"] == pytest.approx(whole["ts_us"])
        assert prefill["ts_us"] == pytest.approx(
            queued["ts_us"] + queued["dur_us"])
        assert decode["ts_us"] == pytest.approx(
            prefill["ts_us"] + prefill["dur_us"])
        assert (queued["dur_us"] + prefill["dur_us"] + decode["dur_us"]
                == pytest.approx(whole["dur_us"]))
        assert whole["dur_us"] == pytest.approx(
            (req.finished_at - req.arrival) * 1e6)
        assert queued["dur_us"] == pytest.approx(
            (req.admitted_at - req.arrival) * 1e6)
        assert prefill["dur_us"] == pytest.approx(
            (req.arrival + req.ttft - req.admitted_at) * 1e6)
    retired = sum(r["attrs"]["retired"] for r in rows["serve.retire"])
    assert retired == 3


def test_a_rejected_request_is_the_parent_span_alone():
    sched = _scheduler()
    trace.enable(buffer_spans=256)
    sched.submit(Request(rid=7, prompt=np.zeros((200,), np.int32),
                         max_new_tokens=2))     # over the 128 ceiling
    sched.step()
    rows = _by_name(trace.snapshot())
    (whole,) = rows["serve.request"]
    assert whole["attrs"]["rid"] == 7 and "exceeds" in whole["attrs"]["error"]
    assert not [n for n in rows if n.startswith("serve.request.")]
    assert rows["serve.admit"][0]["attrs"] == {
        "admitted": 0, "rejected": 1, "queued": 0}


def test_off_records_nothing_and_the_tokens_are_the_same_on_and_off():
    assert not trace.enabled()
    off = _run(_scheduler(), _requests(3))
    assert trace.snapshot() == []
    trace.enable(buffer_spans=4096)
    on = _run(_scheduler(), _requests(3))
    assert trace.snapshot()
    assert on == off


def test_see_counts_every_token_the_host_appends():
    sched = _scheduler()
    trace.enable(buffer_spans=4096)
    requests = _requests(3)
    _run(sched, requests)
    rows = _by_name(trace.snapshot())
    decodes = {d["span_id"] for d in rows["serve.decode"]}
    sees = rows["serve.see"]
    assert sees and all(s["parent_id"] in decodes for s in sees)
    assert sum(s["attrs"]["tokens"] for s in sees) == \
        sum(len(r.tokens) for r in requests)
    assert sum(s["attrs"]["firsts"] for s in sees) == len(requests)
    # a cycle with nothing in flight (a run's first step) reads nothing in
    assert len(sees) == len(rows["serve.decode"]) - 1


def test_the_wait_has_two_halves_on_and_is_one_read_off(monkeypatch):
    calls = []
    ready = type(jnp.zeros(1)).block_until_ready
    monkeypatch.setattr(type(jnp.zeros(1)), "block_until_ready",
                        lambda self: calls.append(1) or ready(self))
    assert not trace.active()
    off = _run(_scheduler(), _requests(2))
    assert calls == []              # the off path: one np.asarray, no more
    trace.enable(buffer_spans=4096)
    sched = _scheduler()
    on = _run(sched, _requests(2))
    sched.engine.drain("idle")
    assert on == off
    rows = trace.snapshot()
    waits = [r for r in rows if r["name"] == "engine.decode.wait"]
    assert waits and len(calls) == len(waits)
    for wait in waits:
        halves = [r for r in rows if r["parent_id"] == wait["span_id"]]
        assert [h["name"] for h in halves] == [
            "engine.decode.wait.ready", "engine.decode.wait.copy"]
        assert all(_inside(h, wait) for h in halves)
        assert halves[0]["ts_us"] + halves[0]["dur_us"] <= \
            halves[1]["ts_us"] + 0.5


def test_a_cycle_carries_its_cpu_and_collection_time(monkeypatch):
    import gc
    sched = _scheduler()
    admit = sched._admit
    monkeypatch.setattr(sched, "_admit",
                        lambda now: (gc.collect(), admit(now))[1])
    trace.enable(buffer_spans=4096)
    _run(sched, _requests(2))
    rows = _by_name(trace.snapshot())
    cycles = rows["serve.cycle"]
    for cycle in cycles:
        a = cycle["attrs"]
        # the thread's own clock, read inside the span: never above its wall
        assert 0.0 <= a["cpu_ms"] <= cycle["dur_us"] * 1e-3 + 1e-3
        full = [g for g in rows["host.gc.gen2"]
                if g["parent_id"] == cycle["span_id"]]
        assert len(full) == 1       # the forced one, under the cycle
        assert a["gc_ms"] >= full[0]["dur_us"] * 1e-3 - 1e-6 > 0
        assert a["gc_ms"] <= cycle["dur_us"] * 1e-3


def test_speculative_decode_has_the_same_boundary():
    sched = _scheduler(draft="truncate:1", spec_k=2)
    trace.enable(buffer_spans=4096)
    _run(sched, _requests(2))
    rows = _by_name(trace.snapshot())
    decode = rows["serve.decode"][0]
    assert {"active", "tokens", "proposed", "accepted"} <= set(
        decode["attrs"])
    assert decode["attrs"]["proposed"] == 2 * decode["attrs"]["active"]
    ids = {d["span_id"] for d in rows["serve.decode"]}
    for name in ("engine.draft.dispatch", "engine.draft.wait",
                 "engine.verify.dispatch", "engine.verify.wait"):
        assert rows[name] and all(r["parent_id"] in ids for r in rows[name])
    assert len(rows["engine.draft.dispatch"]) == \
        2 * len(rows["engine.verify.dispatch"])
    assert "engine.decode.dispatch" not in rows


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events if e.name.startswith("hvd.")]
    return events


def test_a_profiler_session_gets_the_spans_with_the_recorder_off(
        tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path / "hvdtrace"))
    hvd.init()
    sched = _scheduler()
    assert not trace.enabled()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "profile"),
                             profiler_options=options)
    try:
        tokens = _run(sched, _requests(2))
    finally:
        jax.profiler.stop_trace()
    assert tokens == _run(_scheduler(), _requests(2))
    # no ring, no state change, nothing exported at shutdown
    assert not trace.enabled() and trace.snapshot() == []
    hvd.shutdown()
    assert not os.path.exists(tmp_path / "hvdtrace")
    events = _host_events(str(tmp_path / "profile"))
    names = {n for n, _, _ in events}
    assert {"hvd.serve.cycle", "hvd.serve.retire", "hvd.serve.admit",
            "hvd.serve.prefill", "hvd.serve.decode",
            "hvd.engine.prefill.dispatch", "hvd.engine.decode.dispatch",
            "hvd.engine.decode.ahead", "hvd.engine.decode.wait"} <= names
    # nothing waits for a prompt's last chunk while a decode step follows
    assert "hvd.engine.prefill.wait" not in names
    # a request's spans are written after the fact: ring only
    assert not [n for n in names if n.startswith("hvd.serve.request")]
    cycles = sorted((s, s + d) for n, s, d in events
                    if n == "hvd.serve.cycle")
    assert len(cycles) == sched._cycles
    dispatches = sorted((s, s + d) for n, s, d in events
                        if n == "hvd.engine.decode.dispatch")
    for n, s, d in events:
        if n == "hvd.engine.decode.dispatch":
            assert any(lo <= s and s + d <= hi for lo, hi in cycles)
        if n == "hvd.engine.decode.ahead":
            assert any(lo <= s and s + d <= hi for lo, hi in dispatches)
    # after the session the off path is the shared no-op again
    assert trace.span("a") is trace.span("b")


def test_a_profiler_session_gets_the_read_in_and_the_collections(tmp_path):
    """The spans an idle gap of the device is named by, with no knob set:
    the read-in, the two halves of the readback, a collection."""
    import gc
    sched = _scheduler()            # its engine put the collector's hook in
    assert not trace.enabled()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "profile"),
                             profiler_options=options)
    try:
        assert trace.active()
        for r in _requests(2):
            sched.submit(r)
        sched.step()
        gc.collect()
        _run(sched, [])
        while len(sched.completed) < 2:
            sched.step()
    finally:
        jax.profiler.stop_trace()
    assert not trace.active() and trace.snapshot() == []
    events = _host_events(str(tmp_path / "profile"))
    names = {n for n, _, _ in events}
    assert {"hvd.serve.see", "hvd.engine.decode.wait.ready",
            "hvd.engine.decode.wait.copy", "hvd.host.gc.gen2"} <= names
    waits = sorted((s, s + d) for n, s, d in events
                   if n == "hvd.engine.decode.wait")
    for n, s, d in events:
        if n.startswith("hvd.engine.decode.wait."):
            assert any(lo <= s and s + d <= hi for lo, hi in waits)


def test_recorder_on_writes_ring_and_profiler_annotation(tmp_path):
    sched = _scheduler()
    trace.enable(buffer_spans=4096)
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        _run(sched, _requests(1))
    finally:
        jax.profiler.stop_trace()
    ring = [r for r in trace.snapshot() if r["name"] == "serve.cycle"]
    annotated = [e for e in _host_events(str(tmp_path / "profile"))
                 if e[0] == "hvd.serve.cycle"]
    assert len(ring) == len(annotated) == sched._cycles


def test_the_engines_device_programs_carry_names():
    sched = _scheduler(prefix_cache=True, draft="truncate:1", spec_k=2)
    engine = sched.engine
    module = {label: re.match(r"HloModule (\S+?),",
                              engine.executable_text(label)).group(1)
              for label in engine.store_outcomes}
    assert module == {
        "serve_decode": "jit_hvd_serve_decode",
        "serve_first_token": "jit_hvd_serve_token",
        "serve_prefill_32": "jit_hvd_serve_prefill",
        "serve_verify_k2": "jit_hvd_serve_decode",
        "serve_draft_l1": "jit_hvd_serve_draft",
        "serve_cow_copy": "jit_hvd_serve_cow"}
    lowered = engine._decode_jit.lower(*engine._decode_args()).as_text()
    assert "@jit_hvd_serve_decode" in lowered
    lowered = engine._prefill_jit.lower(*engine._prefill_args(32)).as_text()
    assert "@jit_hvd_serve_prefill" in lowered
    for label in ("serve_decode", "serve_prefill_32"):
        names = set(re.findall(r'op_name="([^"]*)"',
                               engine.executable_text(label)))
        for scope in ("hvd_attention", "hvd_mlp", "hvd_kv_write"):
            assert any(f"/{scope}/" in n for n in names), (label, scope)


def _train_step_text():
    import optax
    from horovod_tpu.parallel import trainer
    hvd.init(devices=jax.devices()[:2])
    mesh = hvd.mesh()
    cfg = _cfg(dp_axis=mesh.axis_names[0], max_seq=64, mlp_recompute=True,
               dtype=jnp.bfloat16)
    init, step = trainer.make_transformer_train_step(
        cfg, optax.sgd(0.1, momentum=0.9), mesh)
    state = init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 32), jnp.int32)
    return step.lower(state, tokens, tokens).compile().as_text()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_32",
                                     "train_step"])
def test_no_norm_stands_under_hvd_mlp(program):
    """The block's ``mlp_norm`` stands outside ``hvd_mlp`` in every program
    made of it, as its ``attn_norm`` stands outside ``hvd_attention``: the
    scope's device time is the MLP's products and activation."""
    text = (_train_step_text() if program == "train_step"
            else _scheduler().engine.executable_text(program))
    norms = [m.group(1) for m in re.finditer(
        r' rsqrt\(.*op_name="([^"]*)"', text)]
    assert norms                        # the norms are there, and named
    assert not [n for n in norms if "hvd_mlp" in n or "hvd_attention" in n]
    assert any("hvd_mlp" in n for n in re.findall(r'op_name="([^"]*)"', text))


def test_the_train_steps_scopes_are_in_the_compiled_hlo():
    text = _train_step_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))

    def some(scope, *parts):
        word = re.compile(rf"(?<![A-Za-z0-9_]){scope}(?![A-Za-z0-9_])")
        return any(word.search(n) and all(p in n for p in parts)
                   for n in names)

    # forward under jvp, backward under transpose(jvp): a scope outside
    # the layer scan reads "transpose(jvp(hvd_loss))/...", one inside it
    # "transpose(jvp())/while/body/closed_call/hvd_attention/..."
    for scope in ("hvd_attention", "hvd_mlp", "hvd_loss"):
        assert some(scope, "jvp("), scope
        assert some(scope, "transpose(jvp("), scope
    assert some("hvd_loss", "transpose(jvp(hvd_loss))")
    assert some("hvd_attention", "transpose(jvp())", "/hvd_attention/")
    assert some("hvd_grad_sync") and some("hvd_optimizer")
    assert some("hvd_optimizer", "hvd_unfused_apply")
    # the sync is the collective, and nothing of the model is inside it
    assert any("all-reduce" in line and "hvd_grad_sync" in line
               for line in text.splitlines())
    assert not some("hvd_grad_sync", "hvd_attention")
