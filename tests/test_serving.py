"""Serving subsystem tests (docs/serving.md): paged-decode kernel
equivalence, engine-vs-training-model numerics, continuous-batching
determinism, the warm-boot compile-free gate, train->serve handoff, and
the serving observability surface."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.pallas import flash_attention as fa
from horovod_tpu.serving import kv_cache as kvc
from horovod_tpu.serving import (PageAllocator, Request, ServeEngine,
                                 ServeScheduler)
from horovod_tpu.serving.engine import prefill_buckets


@pytest.fixture(scope="module", autouse=True)
def _shared_store(tmp_path_factory):
    """One artifact store for the whole module: the first boot of each
    executable geometry compiles and publishes, every later boot loads
    warm — the production warm-replica path doubling as a test-suite
    speedup. The warm-boot gate tests monkeypatch their own fresh store
    dir on top of this (and reset the singleton), so their cold-miss
    assertions are unaffected."""
    from horovod_tpu.store import artifact_store
    d = tmp_path_factory.mktemp("serving-store")
    old = os.environ.get("HOROVOD_ARTIFACT_STORE")
    os.environ["HOROVOD_ARTIFACT_STORE"] = str(d)
    artifact_store.reset_for_tests()
    yield
    if old is None:
        os.environ.pop("HOROVOD_ARTIFACT_STORE", None)
    else:
        os.environ["HOROVOD_ARTIFACT_STORE"] = old
    artifact_store.reset_for_tests()


def _cfg(**kw):
    base = dict(vocab_size=256, d_model=64, n_heads=4, head_dim=16,
                n_layers=2, d_ff=128, max_seq=256, dtype=jnp.float32,
                dp_axis=None, remat=False)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _engine(cfg=None, params=None, **kw):
    cfg = cfg or _cfg()
    if params is None:
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    kw.setdefault("slots", 4)
    kw.setdefault("page", 16)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prefill_chunk", 64)
    return ServeEngine(cfg, params, mesh=None, **kw), params


# ---------------------------------------------------------------------------
# paged decode attention: kernel (interpret) == jnp reference == dense
# ---------------------------------------------------------------------------

def _rand_paged(rng, b, h, kvh, d, page, n_max, n_pages,
                dtype=jnp.float32):
    """Queries ``[b, h, d]`` and a K and a V pool of the dense row: a
    token's ``kvh`` heads side by side, ``[n_pages + 1, page, kvh * d]``."""
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((n_pages + 1, page, kvh * d)),
                     dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages + 1, page, kvh * d)),
                     dtype)
    bt = jnp.asarray(
        rng.permutation(n_pages)[:b * n_max].reshape(b, n_max), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, page * n_max + 1, b), jnp.int32)
    return q, kp, vp, bt, lengths


@pytest.mark.parametrize("b,h,kvh,d,page,n_max,dtype", [
    (2, 4, 4, 128, 128, 3, jnp.float32),      # lane-aligned page, MHA
    (3, 4, 2, 64, 128, 2, jnp.float32),       # GQA grouping, short head dim
    (1, 2, 2, 128, 256, 2, jnp.float32),      # multi-lane page
    (4, 8, 4, 16, 32, 3, jnp.float32),        # small page and head dim, GQA
    (2, 16, 16, 64, 128, 3, jnp.float32),     # Pythia-410m's heads and page
    (2, 32, 8, 128, 128, 2, jnp.float32),     # granite-4.0-h-small's
    (2, 16, 16, 64, 128, 3, jnp.bfloat16),    # the pool as it is served
])
def test_paged_kernel_matches_reference(b, h, kvh, d, page, n_max, dtype):
    """The interpret-mode kernel is pinned against the jnp paged
    reference across page sizes, GQA grouping, and ragged lengths, on
    the pool's flat row. A bfloat16 pool meets the same tolerances: its
    products are exact and the probabilities stay float32."""
    rng = np.random.default_rng(0)
    q, kp, vp, bt, lengths = _rand_paged(rng, b, h, kvh, d, page, n_max,
                                         b * n_max + 2, dtype)
    scale = d ** -0.5
    out = fa.flash_paged_decode(q, kp, vp, bt, lengths, scale,
                                interpret=True)
    ref = kvc.paged_attention_reference(q, kp, vp, bt, lengths, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_paged_reference_matches_dense():
    """The paged path (any page size, block-table indirection) equals
    dense single-query attention over the contiguous prefix."""
    rng = np.random.default_rng(1)
    b, h, d, page, n_max = 3, 4, 32, 16, 4          # non-kernel page size
    q, kp, vp, bt, lengths = _rand_paged(rng, b, h, h, d, page, n_max,
                                         b * n_max + 2)
    out = kvc.paged_attention_reference(q, kp, vp, bt, lengths,
                                        d ** -0.5)
    for i in range(b):
        k = np.asarray(kvc.gather_pages(kp, bt[i]))[:int(lengths[i])]
        v = np.asarray(kvc.gather_pages(vp, bt[i]))[:int(lengths[i])]
        k, v = k.reshape(-1, h, d), v.reshape(-1, h, d)
        s = np.einsum("hd,shd->hs", np.asarray(q[i]), k) * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        dense = np.einsum("hs,shd->hd", p, v)
        np.testing.assert_allclose(np.asarray(out[i]), dense,
                                   rtol=1e-5, atol=1e-5)


def test_paged_kernel_empty_slot_returns_zeros():
    rng = np.random.default_rng(2)
    q, kp, vp, bt, _ = _rand_paged(rng, 2, 2, 2, 128, 128, 2, 6)
    lengths = jnp.asarray([5, 0], jnp.int32)
    out = fa.flash_paged_decode(q, kp, vp, bt, lengths, 0.1,
                                interpret=True)
    assert np.all(np.asarray(out[1]) == 0.0)
    ref = kvc.paged_attention_reference(q, kp, vp, bt, lengths, 0.1)
    assert np.all(np.isfinite(np.asarray(ref)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_paged_decode_supports_gates_non_dividing_shapes():
    q = jnp.zeros((2, 4, 128))
    ok = jnp.zeros((8, 128, 4 * 128))
    assert fa.paged_decode_supports(q, ok)
    # a block is a whole page: the page size is free
    assert fa.paged_decode_supports(q, jnp.zeros((8, 16, 4 * 128)))
    # 3 KV heads under 4 query heads; a row that is no whole heads of 128
    assert not fa.paged_decode_supports(q, jnp.zeros((8, 128, 3 * 128)))
    assert not fa.paged_decode_supports(q, jnp.zeros((8, 128, 4 * 96 + 64)))
    # the row by head is the old pool, not this kernel's
    assert not fa.paged_decode_supports(q, jnp.zeros((8, 128, 4, 128)))
    assert not fa.paged_decode_supports(
        q.astype(jnp.bfloat16), ok)              # dtype mismatch
    # GQA grouping IS supported when heads divide
    assert fa.paged_decode_supports(q, jnp.zeros((8, 128, 2 * 128)))


# ---------------------------------------------------------------------------
# engine vs the training model (teacher-forced)
# ---------------------------------------------------------------------------

def test_engine_matches_training_model_teacher_forced():
    """Prefill + paged decode reproduce the training ``logits_fn``:
    greedy tokens identical, full-sequence numerics within dtype
    tolerance — across a chunk-crossing prompt and several steps."""
    eng, params = _engine()
    cfg = eng.cfg
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 70).astype(np.int32)  # 2 chunks
    slot = eng.reserve(len(prompt) + 8)
    tok = eng.prefill(slot, prompt)
    seq = list(prompt)
    # One compiled program for every length: the model is causal, so the
    # logits at the last real position ignore the zero padding after it.
    logits = jax.jit(lambda t: tfm.logits_fn(cfg, params, t))

    def last_logits(seq):
        padded = np.zeros((1, len(prompt) + 8), np.int32)
        padded[0, :len(seq)] = seq
        return np.asarray(logits(jnp.asarray(padded)))[0, len(seq) - 1]

    assert tok == int(np.argmax(last_logits(seq)))
    seq.append(tok)
    for _ in range(6):
        tokens = np.zeros((eng.slots,), np.int32)
        tokens[slot] = seq[-1]
        nxt = eng.decode_step(tokens)
        assert int(nxt[slot]) == int(np.argmax(last_logits(seq)))
        seq.append(int(nxt[slot]))


def test_engine_rejects_unsupported_parallelism_and_long_prompts():
    with pytest.raises(ValueError, match="dense TP/DP"):
        ServeEngine(_cfg(sp_axis="sp"), {}, mesh=None)
    with pytest.raises(ValueError, match="dense TP/DP"):
        ServeEngine(_cfg(num_experts=2), {}, mesh=None)
    eng, _ = _engine()
    slot = eng.reserve(16)
    with pytest.raises(ValueError, match="HOROVOD_SERVE_MAX_SEQ"):
        eng.prefill(slot, np.zeros(4096, np.int32))


def test_prefill_buckets_cover_chunk_cap():
    assert prefill_buckets(256) == [32, 64, 128, 256]
    assert prefill_buckets(96) == [32, 64, 96]
    eng, _ = _engine()
    assert eng.bucket_for(1) == 32
    assert eng.bucket_for(33) == 64
    assert eng.bucket_for(10 ** 6) == eng.buckets[-1]


# ---------------------------------------------------------------------------
# paged cache allocator
# ---------------------------------------------------------------------------

def test_page_allocator_freelist_and_exhaustion():
    a = PageAllocator(4)
    got = a.alloc(3)
    assert len(set(got)) == 3 and a.free_pages == 1
    assert not a.can_alloc(2)
    with pytest.raises(MemoryError, match="HOROVOD_SERVE_PAGES"):
        a.alloc(2)
    a.free(got)
    assert a.free_pages == 4
    with pytest.raises(ValueError):
        a.free([99])


def test_engine_admission_blocks_on_pages_and_eviction_frees():
    eng, _ = _engine(slots=2, max_seq=64)        # 2 slots x 4 pages
    s0 = eng.reserve(60)                         # 4 pages
    s1 = eng.reserve(60)
    assert s0 is not None and s1 is not None
    assert eng.reserve(16) is None               # no slot left
    eng.release(s0)
    assert eng.allocator.free_pages == 4         # eviction-on-finish
    assert eng.reserve(16) is not None


# ---------------------------------------------------------------------------
# continuous batching: solo == batched, bitwise
# ---------------------------------------------------------------------------

def _greedy_solo(eng, prompt, n_new):
    slot = eng.reserve(len(prompt) + n_new)
    tokens = [eng.prefill(slot, prompt)]
    for _ in range(n_new - 1):
        t = np.zeros((eng.slots,), np.int32)
        t[slot] = tokens[-1]
        tokens.append(int(eng.decode_step(t)[slot]))
    eng.release(slot)
    return tokens


def test_continuous_batching_outputs_bitwise_equal_solo():
    """The acceptance bit: a request's tokens under continuous batching
    (arbitrary slot, co-tenants mid-flight) are identical to the same
    request run alone — slot index and page assignment change WHERE the
    bytes live, never the values a row reduces over."""
    eng, params = _engine()
    cfg = eng.cfg
    rng = np.random.default_rng(4)
    # up to 100 tokens: several prompts span multiple prefill chunks
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, 100))).astype(np.int32)
               for _ in range(6)]
    n_new = 8
    solo = [_greedy_solo(eng, p, n_new) for p in prompts]

    sched = ServeScheduler(eng, queue_deadline=0.0)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    done = sched.run(reqs)
    assert len(done) == len(prompts)
    by_rid = {r.rid: r for r in done}
    for i in range(len(prompts)):
        assert by_rid[i].tokens == solo[i], f"request {i} diverged"


def test_prefill_interleaves_one_chunk_per_cycle():
    """A long prompt prefills ONE chunk per scheduling cycle — decode
    steps run between its chunks, so co-tenants' TPOT never stalls for
    the whole prompt. A cycle dispatches the co-tenant's next step and
    shows the host the step before."""
    eng, _ = _engine(prefill_chunk=32)
    sched = ServeScheduler(eng, queue_deadline=0.0)
    rng = np.random.default_rng(7)
    short = Request(rid=0, prompt=rng.integers(0, 256, 8).astype(np.int32),
                    max_new_tokens=10)
    sched.submit(short)
    sched.step()                                 # short admitted+decoding
    assert sched.active and not sched.prefilling
    assert short._dispatched == 2                # first token + one step
    long = Request(rid=1,
                   prompt=rng.integers(0, 256, 90).astype(np.int32),
                   max_new_tokens=4)
    sched.submit(long)
    sched.step()                                 # chunk 1 of 3 (32 toks)
    assert long.slot in sched.prefilling
    assert long._prefill_pos == 32 and long.tokens == []
    assert short._dispatched == 3                # decode ran anyway
    tokens_before = len(short.tokens)
    sched.step()                                 # chunk 2 of 3
    assert long.slot in sched.prefilling
    assert long._prefill_pos == 64
    assert short._dispatched == 4
    assert len(short.tokens) == tokens_before + 1
    sched.step()                                 # chunk 3 -> first token
    assert long.slot not in sched.prefilling
    assert long._dispatched == 2                 # it joined this very step
    assert len(short.tokens) == tokens_before + 2
    sched.run()                                  # drain
    assert {r.rid for r in sched.completed} == {0, 1}
    assert len(long.tokens) == 4 and len(short.tokens) == 10
    assert long.tokens == _greedy_solo(eng, long.prompt, 4)


def test_max_new_tokens_cap_is_exact_and_eos_stops_at_prefill():
    """A cap of 1 (or EOS emitted by prefill) must not decode one token
    past it — the retire between admit and decode."""
    eng, params = _engine()
    sched = ServeScheduler(eng, queue_deadline=0.0)
    prompt = np.arange(8, dtype=np.int32)
    done = sched.run([Request(rid=0, prompt=prompt, max_new_tokens=1)])
    assert len(done[0].tokens) == 1
    # EOS at the prefill token: generation stops there too
    first = _greedy_solo(eng, prompt, 1)[0]
    sched2 = ServeScheduler(eng, queue_deadline=0.0)
    done2 = sched2.run([Request(rid=0, prompt=prompt, max_new_tokens=50,
                                eos_token=first)])
    assert done2[0].tokens == [first]


# ---------------------------------------------------------------------------
# one step late: step c is enqueued before step c-1 is read
# ---------------------------------------------------------------------------

def _late_engine(model, **kw):
    """A small engine of either served model, 3 slots of 64 tokens."""
    kw = {"slots": 3, "page": 8, "max_seq": 64, "prefill_chunk": 32, **kw}
    if model == "dense":
        return _engine(**kw)[0]
    from test_longcat_flash import _cfg as lc_cfg, _params as lc_params
    cfg = lc_cfg()
    return ServeEngine(cfg, lc_params(cfg), None, prefix_cache=False,
                       draft="off", **kw)


# (prompt tokens, max_new_tokens, index of the token taken as EOS or None)
LATE_CASES = {
    # met by the prefill token: never decodes
    "cap_of_one": [(9, 1, None), (20, 1, None), (13, 5, None)],
    # caps met mid-flight, at different steps, a fourth request queued
    "cap_mid_flight": [(9, 2, None), (12, 3, None), (17, 7, None),
                       (11, 4, None)],
    # an EOS mid-stream, one that the prefill itself emits, none
    "eos_mid_stream": [(9, 12, 3), (14, 12, 0), (11, 6, None)],
    # 8 requests over 3 slots: a request is admitted into a slot released
    # a cycle earlier, while a step dispatched before the release is queued
    "slot_reuse": [(5 + 3 * i, 2 + i % 5, None) for i in range(8)],
    # prompts of two chunks (32 + 13, 32 + 18) beside a running batch
    "chunks_interleaved": [(9, 12, None), (45, 5, None), (50, 6, None)],
}


@pytest.mark.parametrize("mode", ["continuous", "static"])
@pytest.mark.parametrize("case", sorted(LATE_CASES))
@pytest.mark.parametrize("model", ["dense", "longcat"])
def test_one_step_late_equals_the_synchronous_loop(model, case, mode):
    """Through the scheduler (step c enqueued before step c-1 is read,
    tokens fed from step to step on the device) every request's tokens
    are the direct loop's (``prefill`` + ``decode_step(<NumPy>)``, each
    step read before the next). A request ends by what was dispatched
    for it: exactly ``max_new_tokens`` tokens and no decode step past
    them; an EOS is seen one step late and what was dispatched
    meanwhile is discarded."""
    eng = _late_engine(model)
    rng = np.random.default_rng(32)
    reqs, want = [], {}
    for rid, (n_prompt, cap, eos_at) in enumerate(LATE_CASES[case]):
        prompt = rng.integers(0, eng.cfg.vocab_size, n_prompt).astype(
            np.int32)
        solo = _greedy_solo(eng, prompt, cap)
        eos = None
        if eos_at is not None:
            # the first token from there on that did not occur before
            eos_at = next(k for k in range(eos_at, cap)
                          if solo[k] not in solo[:k])
            eos, solo = solo[eos_at], solo[:eos_at + 1]
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=cap,
                            eos_token=eos))
        want[rid] = solo
    sched = ServeScheduler(eng, mode=mode, queue_deadline=0.0)
    steps_in = {r.rid: 0 for r in reqs}      # decode steps it was part of
    real = eng.decode_step

    def counting(tokens, active=None):
        assert tokens is None                # fed on the device
        for slot in np.flatnonzero(active):
            steps_in[sched.active[int(slot)].rid] += 1
        return real(tokens, active=active)

    eng.decode_step = counting
    done = sched.run(reqs)
    assert {r.rid: r.tokens for r in done} == want
    for r in done:
        assert r.error is None and len(r.tpot) == len(r.tokens) - 1
        if r.eos_token is None:
            assert steps_in[r.rid] == r.max_new_tokens - 1
        else:       # the steps dispatched before the host saw the EOS
            assert len(r.tokens) - 1 <= steps_in[r.rid] <= len(r.tokens) + 1
    # nothing in flight, every slot and page back
    assert eng._unread is None and sched._in_flight is None
    assert eng.occupancy() == 0.0
    assert eng.allocator.free_pages == eng.pool.n_pages
    counts = eng.stats()["decode"]
    assert counts["steps"] >= max(steps_in.values())
    assert set(counts["drained"]) <= {"idle", "direct"}


@pytest.mark.parametrize("model", ["dense", "longcat"])
def test_a_steady_run_never_drains(model):
    """While every slot decodes, each step but the first is enqueued with
    the step before it unread, and the host never reads a step that has
    nothing queued behind it."""
    eng = _late_engine(model)
    rng = np.random.default_rng(3)
    sched = ServeScheduler(eng, queue_deadline=0.0)
    for rid in range(eng.slots):
        sched.submit(Request(
            rid=rid, prompt=rng.integers(0, eng.cfg.vocab_size, 9 + rid)
            .astype(np.int32), max_new_tokens=20))
    for _ in range(12):
        sched.step()
    counts = eng.stats()["decode"]
    assert counts == {"steps": 12, "dispatched_ahead": 11, "drained": {}}
    # the host has seen every step but the one in flight
    assert [len(r.tokens) for r in sched.active.values()] == [12] * eng.slots
    assert [r._dispatched for r in sched.active.values()] == [13] * eng.slots
    assert eng._unread is not None
    sched.run()
    counts = eng.stats()["decode"]
    assert counts["steps"] == 19 and counts["dispatched_ahead"] == 18
    assert counts["drained"] == {"idle": 1}     # the last step of the run
    assert eng._unread is None and sched._in_flight is None


def test_speculation_reads_every_step_before_it_goes_on():
    """Acceptance needs the values: a speculating scheduler never runs
    ahead, and its tokens are the sequential sequence all the same."""
    solo_eng, params = _engine(slots=2)
    rng = np.random.default_rng(18)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (9, 40)]
    solo = [_greedy_solo(solo_eng, p, 9) for p in prompts]
    eng, _ = _engine(slots=2, params=params, draft="ngram", spec_k=3)
    sched = ServeScheduler(eng, queue_deadline=0.0)
    done = sched.run([Request(rid=i, prompt=p, max_new_tokens=9)
                      for i, p in enumerate(prompts)])
    assert [r.tokens for r in sorted(done, key=lambda r: r.rid)] == solo
    counts = eng.stats()["decode"]
    assert counts["steps"] == counts["dispatched_ahead"] == 0
    assert counts["drained"] == {"speculation": sched.stats()["decode_steps"]}
    assert eng._unread is None and sched._in_flight is None


def test_requests_clamped_or_rejected_at_context_ceiling():
    """prompt+max_new past HOROVOD_SERVE_MAX_SEQ is clamped (decoding
    past the last reserved page would corrupt the cache); an
    over-ceiling prompt is rejected with the reason, not admitted."""
    eng, _ = _engine(max_seq=64)
    sched = ServeScheduler(eng, queue_deadline=0.0)
    ok = Request(rid=0, prompt=np.arange(60, dtype=np.int32),
                 max_new_tokens=100)
    too_long = Request(rid=1, prompt=np.arange(80, dtype=np.int32),
                       max_new_tokens=4)
    exact = Request(rid=2, prompt=np.arange(64, dtype=np.int32),
                    max_new_tokens=4)          # == ceiling: accepted
    done = sched.run([ok, too_long, exact])
    by_rid = {r.rid: r for r in done}
    assert len(by_rid[0].tokens) == 4          # clamped to 64 - 60
    assert by_rid[0].error is None
    assert by_rid[1].tokens == []
    assert "HOROVOD_SERVE_MAX_SEQ" in by_rid[1].error
    # a prompt of exactly max_seq admits; its one free token comes
    # from prefill (max_new clamps to 0)
    assert by_rid[2].error is None and len(by_rid[2].tokens) == 1
    # the engine-level guard backs the scheduler's clamp
    with pytest.raises(ValueError, match="clamp max_new_tokens"):
        eng.reserve(1000)


def test_request_larger_than_pool_rejected_not_livelocked():
    """A worst case bigger than the WHOLE page pool can never be
    satisfied by retiring — it must reject (with the pool named), not
    head-of-line-block the queue and spin run() forever."""
    eng, _ = _engine(slots=2, max_seq=64, n_pages=2)    # pool: 32 tokens
    sched = ServeScheduler(eng, queue_deadline=0.0)
    rng = np.random.default_rng(8)
    big = Request(rid=0, prompt=rng.integers(0, 256, 40).astype(np.int32),
                  max_new_tokens=20)                    # 4 pages > 2
    small = Request(rid=1, prompt=rng.integers(0, 256, 8).astype(np.int32),
                    max_new_tokens=4)                   # 1 page: fits
    done = sched.run([big, small])
    by_rid = {r.rid: r for r in done}
    assert "HOROVOD_SERVE_PAGES" in by_rid[0].error
    assert by_rid[1].error is None and len(by_rid[1].tokens) == 4


def test_decode_step_advances_lengths_at_dispatch_over_a_snapshot():
    """The dispatch is asynchronous and an upload may alias the NumPy
    buffer it is handed (zero-copy jnp.asarray on the CPU backend): the
    step gets COPIES of the tables and lengths, so the host may advance
    its lengths as soon as the step is enqueued, before anything is read
    back, and the step still reads the lengths it was dispatched with."""
    eng, _ = _engine(slots=2)
    rng = np.random.default_rng(21)
    tokens = np.zeros((eng.slots,), np.int32)
    for _ in range(eng.slots):                  # every slot active
        prompt = rng.integers(0, 256, 9).astype(np.int32)
        slot = eng.reserve(len(prompt) + 4)
        tokens[slot] = eng.prefill(slot, prompt)
    before = eng.tables.lengths.copy()
    seen = {}

    class Readback:
        def __init__(self, arr):
            self.arr = arr

        def __array__(self, dtype=None, copy=None):
            seen["at_readback"] = eng.tables.lengths.copy()
            return np.asarray(self.arr)

    real = eng._decode

    def spy(*args):
        seen["tables"], seen["lengths"] = args[-3], args[-2]
        seen["at_dispatch"] = eng.tables.lengths.copy()
        k, v, nxt, logits = real(*args)
        return k, v, Readback(nxt), logits

    eng._decode = spy
    eng.decode_step(tokens)
    np.testing.assert_array_equal(seen["at_dispatch"], before)
    np.testing.assert_array_equal(seen["at_readback"], before + 1)
    np.testing.assert_array_equal(eng.tables.lengths, before + 1)
    # what the step was handed is still what it was dispatched with
    np.testing.assert_array_equal(np.asarray(seen["lengths"]), before)
    assert not np.shares_memory(np.asarray(seen["lengths"]),
                                eng.tables.lengths)
    assert not np.shares_memory(np.asarray(seen["tables"]),
                                eng.tables.tables)


def test_decode_step_default_mask_protects_mid_prefill_slots():
    """Direct-API interleave: a decode_step WITHOUT an explicit active
    mask must not write into (or advance) a slot whose prompt is still
    prefilling — its tokens must come out identical to an undisturbed
    run."""
    eng, _ = _engine(prefill_chunk=32)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 256, 90).astype(np.int32)
    undisturbed = _greedy_solo(eng, prompt, 4)
    slot = eng.reserve(94)
    pos, first = eng.prefill_chunk(slot, prompt, 0)     # chunk 1 of 3
    assert first is None
    eng.decode_step(np.zeros((eng.slots,), np.int32))   # default mask
    assert eng.tables.lengths[slot] == 0                # not advanced
    tokens = None
    while tokens is None:
        pos, tokens = eng.prefill_chunk(slot, prompt, pos)
    out = [tokens]
    for _ in range(3):
        t = np.zeros((eng.slots,), np.int32)
        t[slot] = out[-1]
        out.append(int(eng.decode_step(t)[slot]))
    eng.release(slot)
    assert out == undisturbed


def test_ceiling_error_names_model_context_when_it_binds():
    """When cfg.max_seq (not the knob) is the binding limit, the
    rejection must say so — raising HOROVOD_SERVE_MAX_SEQ cannot fix
    it."""
    cfg = _cfg(max_seq=64)
    eng = ServeEngine(cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)),
                      mesh=None, slots=2, page=16, max_seq=2048,
                      prefill_chunk=32)
    slot = eng.reserve(16)
    with pytest.raises(ValueError, match="model's trained context"):
        eng.prefill(slot, np.zeros(100, np.int32))


def test_static_mode_waits_for_whole_batch():
    eng, _ = _engine(slots=2)
    sched = ServeScheduler(eng, mode="static", queue_deadline=0.0)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, 256, 8).astype(np.int32),
                    max_new_tokens=3 + 4 * (i % 2)) for i in range(4)]
    done = sched.run(reqs)
    assert len(done) == 4
    # static batching: the second pair only starts after the first pair
    # fully drains, so its short request finishes after the first
    # pair's long one (the convoy continuous batching removes)
    finish = sorted((r.finished_at, r.rid) for r in done)
    first_batch = {finish[0][1], finish[1][1]}
    assert first_batch == {0, 1}


# ---------------------------------------------------------------------------
# warm boot through the artifact store (kind=serve)
# ---------------------------------------------------------------------------

def test_warm_boot_is_compile_free(tmp_path, monkeypatch):
    from horovod_tpu.store import artifact_store
    monkeypatch.setenv("HOROVOD_ARTIFACT_STORE", str(tmp_path / "store"))
    artifact_store.reset_for_tests()
    try:
        cold, params = _engine()
        # decode + the first-token program + one prefill a bucket
        assert cold.builds == len(cold.buckets) + 2
        assert set(cold.store_outcomes.values()) == {"miss"}
        warm, _ = _engine(cfg=cold.cfg, params=params)
        assert warm.builds == 0
        assert set(warm.store_outcomes.values()) == {"hit"}
        # the warm engine actually serves
        slot = warm.reserve(20)
        tok = warm.prefill(slot, np.arange(10, dtype=np.int32))
        t = np.zeros((warm.slots,), np.int32)
        t[slot] = tok
        warm.decode_step(t)
        # entries landed under the serve kind (header check)
        import struct
        kinds = set()
        for name in os.listdir(tmp_path / "store"):
            raw = open(tmp_path / "store" / name, "rb").read()
            hlen, = struct.unpack(
                ">I", raw[len(artifact_store.MAGIC):
                          len(artifact_store.MAGIC) + 4])
            hdr = json.loads(
                raw[len(artifact_store.MAGIC) + 4:][:hlen])
            kinds.add(hdr["kind"])
        assert kinds == {"serve"}
    finally:
        artifact_store.reset_for_tests()


# ---------------------------------------------------------------------------
# train -> serve handoff
# ---------------------------------------------------------------------------

def _train_state_with_residual(cfg):
    """A TrainState as the training loop checkpoints it: params +
    optimizer state carrying a WireState error-feedback residual."""
    from horovod_tpu.parallel.distributed import WireState
    from horovod_tpu.parallel.trainer import TrainState
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    residual = WireState(jax.tree.map(
        lambda x: jnp.zeros((1,) + x.shape, jnp.float32), params))
    momentum = jax.tree.map(jnp.zeros_like, params)
    return TrainState(jnp.asarray(9, jnp.int32), params,
                      (momentum, residual))


def test_load_for_serving_drops_optimizer_and_residual(tmp_path):
    from horovod_tpu.resilience import AsyncCheckpointer
    from horovod_tpu.serving import load_for_serving
    cfg = _cfg()
    state = _train_state_with_residual(cfg)
    d = str(tmp_path / "ckpt")
    with AsyncCheckpointer(d, interval=0, fmt="pickle") as ck:
        ck.save(9, state, sync=True)
    step, params = load_for_serving(d, mesh=None, cfg=cfg)
    assert step == 9
    # param tree restored exactly; optimizer/residual leaves dropped
    assert jax.tree.structure(params) == jax.tree.structure(state.params)
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  np.asarray(state.params["embed"]))
    n_leaves = len(jax.tree.leaves(params))
    assert n_leaves == len(jax.tree.leaves(state.params))
    # and the restored params actually serve
    eng = ServeEngine(cfg, params, mesh=None, slots=2, page=16,
                      max_seq=64, prefill_chunk=32)
    slot = eng.reserve(12)
    eng.prefill(slot, np.arange(8, dtype=np.int32))


def test_load_for_serving_errors_name_the_fix(tmp_path):
    from horovod_tpu.resilience import AsyncCheckpointer
    from horovod_tpu.resilience.async_checkpoint import (
        CheckpointMismatchError, MANIFEST_NAME, step_dirname)
    from horovod_tpu.serving import load_for_serving
    cfg = _cfg()
    with pytest.raises(FileNotFoundError, match="HOROVOD_CKPT_DIR"):
        load_for_serving(str(tmp_path / "nope"), mesh=None, cfg=cfg)
    # world-mismatched non-replicated shards: the documented reshard
    # path (orbax + template) must be named
    d = str(tmp_path / "ckpt")
    with AsyncCheckpointer(d, interval=0, fmt="pickle") as ck:
        ck.save(3, _train_state_with_residual(cfg), sync=True)
    mpath = os.path.join(d, step_dirname(3), MANIFEST_NAME)
    manifest = json.load(open(mpath))
    manifest["world_size"] = 16
    manifest["shard_digests"] = ["a", "b"]
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(CheckpointMismatchError,
                       match="restore_checkpoint\\(template=...\\)"):
        load_for_serving(d, mesh=None, cfg=cfg)
    # a wrong-model snapshot names the structure mismatch
    d2 = str(tmp_path / "ckpt2")
    with AsyncCheckpointer(d2, interval=0, fmt="pickle") as ck:
        ck.save(1, {"params": {"not_a_transformer": jnp.ones(3)}},
                sync=True)
    with pytest.raises(ValueError, match="different model"):
        load_for_serving(d2, mesh=None, cfg=cfg)


# ---------------------------------------------------------------------------
# observability: metrics, /healthz block, ledger record
# ---------------------------------------------------------------------------

def test_latency_buckets_resolve_sub_millisecond():
    from horovod_tpu import metrics as M
    assert M.LATENCY_BUCKETS[0] < 0.001
    assert sum(1 for b in M.LATENCY_BUCKETS if b < 0.001) >= 3
    assert tuple(M.LATENCY_BUCKETS) == tuple(sorted(M.LATENCY_BUCKETS))


def test_serving_metrics_healthz_and_ledger_block(tmp_path):
    from horovod_tpu import metrics as M
    from horovod_tpu.goodput import ledger
    eng, _ = _engine()
    sched = ServeScheduler(eng, queue_deadline=0.0)
    pre = M.get_registry().get("hvd_serve_ttft_seconds")
    ttft0 = pre.total_count if pre is not None else 0
    rng = np.random.default_rng(6)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, 256, 12).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    sched.run(reqs)
    # the hvd_serve_* family observed traffic
    assert M.get_registry().get(
        "hvd_serve_requests_total").value >= 6       # submitted+admitted
    assert M.get_registry().get("hvd_serve_tokens_total").value > 0
    ttft = M.get_registry().get("hvd_serve_ttft_seconds")
    assert ttft is not None and ttft.total_count - ttft0 == 3
    assert ttft.buckets == tuple(sorted(M.LATENCY_BUCKETS))
    # /healthz carries the serving block
    h = M.health_snapshot()
    assert h["serving"]["engine"]["slots"] == eng.slots
    assert h["serving"]["scheduler"]["completed"] == 3
    # the goodput ledger records the serve block
    rec = ledger.build_record()
    assert rec["serve"]["engine"]["builds"] == eng.builds
    assert rec["serve"]["scheduler"]["completed"] == 3

# ---------------------------------------------------------------------------
# hvdspec: refcounted pages, prefix index, copy-on-write, speculation
# ---------------------------------------------------------------------------

def test_page_allocator_refcount_sharing_and_double_free():
    a = PageAllocator(4)
    got = a.alloc(2)
    assert a.held_refs == 2 and a.shared_pages == 0
    a.incref(got[0])                        # second holder
    assert a.shared_pages == 1
    assert not a.decref(got[0])             # first drop: page stays live
    assert a.free_pages == 2 and a.shared_pages == 0
    assert a.decref(got[0])                 # last holder: page freed
    assert a.free_pages == 3
    with pytest.raises(ValueError, match="double free"):
        a.decref(got[0])
    with pytest.raises(ValueError, match="not allocated"):
        a.incref(got[0])
    a.free([got[1]])
    assert a.free_pages == 4 and a.held_refs == 0


def test_prefix_index_match_register_cow_and_eviction():
    a = PageAllocator(8)
    idx = kvc.PrefixIndex(4, a)             # 4-token blocks
    prompt = np.arange(100, 111, dtype=np.int32)        # 11 tokens
    pages = a.alloc(3)
    assert idx.register(prompt, pages) == 2  # only FULL blocks indexed
    assert a.refcount(pages[0]) == 2 and a.refcount(pages[2]) == 1
    # exact prefix: both full blocks match; block 2 is the tail
    m_pages, skip, cow = idx.match(prompt)
    assert m_pages == pages[:2] and skip == 8 and cow is None
    # same-length prompt diverging inside block 1: chain match stops at
    # block 0, the divergence is a partial (COW) match of 2 tokens
    div = prompt.copy()
    div[6] = 9
    m_pages, skip, cow = idx.match(div)
    assert m_pages == pages[:1] and skip == 4
    assert cow == (pages[1], 2)
    # a prompt that IS one full block leaves its last token unprefixed
    # (the tail prefill must produce the first token's logits)
    m_pages, skip, cow = idx.match(prompt[:4])
    assert m_pages == [] and skip == 0 and cow == (pages[0], 3)
    # retire: the index refs keep both indexed pages resident
    a.free(pages)
    assert a.free_pages == 8 - 2
    # eviction is LRU over leaf entries and frees index-only pages
    assert idx.evict(8) == 2
    assert a.free_pages == 8 and len(idx) == 0 and idx.evictions == 2


def test_prefix_reuse_shares_pages_cow_isolates_and_outputs_match_solo():
    eng, _ = _engine(slots=4, prefix_cache=True)   # page=16
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 256, 48).astype(np.int32)    # 3 full pages
    p_a = np.concatenate([shared, rng.integers(0, 256, 10).astype(np.int32)])
    solo_a = _greedy_solo(eng, p_a, 6)      # also seeds the prefix index
    n_live = eng.pool.n_pages - eng.allocator.free_pages
    assert n_live == 3                      # A's full prompt pages stay
    # B: same shared prefix, different tail -> adopts A's 3 pages
    p_b = np.concatenate([shared, rng.integers(0, 256, 7).astype(np.int32)])
    slot_b = eng.reserve(p_b.size + 6, prompt=p_b)
    assert eng.slot_skip[slot_b] == 48
    assert eng.slot_pages[slot_b][:3] == eng.tables.tables[slot_b][:3].tolist()
    for p in eng.slot_pages[slot_b][:3]:
        assert eng.allocator.refcount(p) == 2     # index + B
    assert eng.allocator.shared_pages == 3
    # C: diverges INSIDE page 2 -> blocks 0-1 shared, page 2 copy-on-write
    p_c = p_a.copy()
    p_c[40] = int(p_c[40] + 1) % 256
    slot_c = eng.reserve(p_c.size + 6, prompt=p_c)
    assert eng.slot_skip[slot_c] == 32 + 8        # 2 blocks + partial COW
    assert eng.cow_copies == 1
    shared_ids = set(eng.slot_pages[slot_b][:3])
    # C's writable page (index 2, the COW copy) aliases NO shared page
    assert eng.slot_pages[slot_c][2] not in shared_ids
    assert eng.slot_pages[slot_c][:2] == eng.slot_pages[slot_b][:2]
    eng.release(slot_b)
    eng.release(slot_c)
    # B and C produce bitwise-solo outputs through the scheduler path
    solo_eng, _ = _engine(slots=4)                # sharing OFF baseline
    solo_b = _greedy_solo(solo_eng, p_b, 6)
    solo_c = _greedy_solo(solo_eng, p_c, 6)
    sched = ServeScheduler(eng, queue_deadline=0.0)
    done = sched.run([Request(rid=0, prompt=p_b, max_new_tokens=6),
                      Request(rid=1, prompt=p_c, max_new_tokens=6)])
    by = {r.rid: r for r in done}
    assert by[0].tokens == solo_b
    assert by[1].tokens == solo_c
    assert sched.stats()["prefix"]["hit_rate"] > 0.5


def test_pool_conservation_across_admit_retire_rollback_and_eviction():
    """free + live == n_pages at every step, no matter how many holders
    each live page has; a drained engine (plus a drained index) returns
    to a full free list."""
    eng, _ = _engine(slots=2, max_seq=64, n_pages=6, prefix_cache=True)
    a = eng.allocator

    def conserved():
        live = len({p for pages in eng.slot_pages if pages
                    for p in pages}
                   | {e.page for e in eng.prefix._entries.values()})
        assert a.free_pages + live == eng.pool.n_pages

    rng = np.random.default_rng(12)
    base = rng.integers(0, 256, 34).astype(np.int32)      # 3 pages
    for round_ in range(3):
        prompt = base.copy()
        if round_ == 2:
            prompt[20] = (prompt[20] + 1) % 256           # force COW
        slot = eng.reserve(prompt.size + 8, prompt=prompt)
        assert slot is not None
        conserved()
        eng.prefill(slot, prompt)
        conserved()
        # speculative-style rollback is pure bookkeeping
        eng.tables.lengths[slot] += 3
        eng.rollback(slot, 3)
        conserved()
        eng.release(slot)
        conserved()
    eng.prefix.evict(eng.pool.n_pages)
    assert a.free_pages == eng.pool.n_pages and a.held_refs == 0


def test_prefix_index_eviction_unblocks_admission():
    """Index-held pages are reclaimable capacity: when the free list
    cannot cover a new request, LRU leaves are evicted instead of
    bouncing the admission."""
    eng, _ = _engine(slots=2, max_seq=64, n_pages=4, prefix_cache=True)
    rng = np.random.default_rng(13)
    p1 = rng.integers(0, 256, 33).astype(np.int32)        # 3 pages
    slot = eng.reserve(p1.size + 8, prompt=p1)
    eng.prefill(slot, p1)
    eng.release(slot)
    assert eng.allocator.free_pages == 2                  # 2 pages indexed
    p2 = rng.integers(0, 256, 40).astype(np.int32)        # needs 3 pages
    slot2 = eng.reserve(p2.size + 8, prompt=p2)
    assert slot2 is not None                              # eviction ran
    assert eng.prefix.evictions >= 1
    eng.release(slot2)


def test_prefix_cache_defaults_off_and_release_frees_everything():
    eng, _ = _engine(slots=2, max_seq=64)
    assert eng.prefix is None and not eng.prefix_cache
    s = eng.reserve(40, prompt=np.arange(36, dtype=np.int32))
    assert eng.slot_skip[s] == 0
    eng.prefill(s, np.arange(36, dtype=np.int32))
    eng.release(s)
    assert eng.allocator.free_pages == eng.pool.n_pages


def test_spec_step_accept_prefix_matches_sequential_decode():
    """The verify step's row i is bitwise the token sequential decode
    emits after consuming rows 0..i — correct drafts are all accepted,
    a wrong draft truncates acceptance exactly there, and rollback
    restores the length invariant."""
    eng, _ = _engine(slots=4, draft="ngram:1", spec_k=3)
    rng = np.random.default_rng(14)
    prompt = rng.integers(0, 256, 20).astype(np.int32)
    seq = _greedy_solo(eng, prompt, 6)       # the sequential truth
    slot = eng.reserve(prompt.size + 6)
    first = eng.prefill(slot, prompt)
    assert first == seq[0]
    tokens = np.zeros((eng.slots,), np.int32)
    tokens[slot] = first
    # drafts = the true continuation: every draft must be accepted
    drafts = np.zeros((eng.slots, 3), np.int32)
    drafts[slot] = seq[1:4]
    active = np.zeros((eng.slots,), bool)
    active[slot] = True
    out = eng.spec_step(tokens, drafts, active=active)
    assert out[slot].tolist() == seq[1:5]    # all K drafts + the bonus
    assert eng.tables.lengths[slot] == prompt.size + 4
    # next round with a WRONG middle draft: accept-prefix stops at it
    tokens[slot] = seq[4]
    drafts[slot] = [seq[5], (seq[5] + 1) % 256, 0]
    out = eng.spec_step(tokens, drafts, active=active)
    assert out[slot][0] == seq[5]
    g = 1                                    # draft 0 right, draft 1 wrong
    eng.rollback(slot, (3 + 1) - (g + 1))
    assert eng.tables.lengths[slot] == prompt.size + 4 + 2
    eng.release(slot)


def test_scheduler_bitwise_equal_solo_with_prefix_and_spec():
    """The acceptance bit of hvdspec: per-request outputs under
    continuous batching with prefix sharing AND speculation enabled are
    bitwise-identical to the same requests run alone."""
    solo_eng, params = _engine(slots=4)
    rng = np.random.default_rng(15)
    shared = rng.integers(0, 256, 40).astype(np.int32)
    prompts = []
    for i in range(6):
        tail = rng.integers(0, 256, int(rng.integers(5, 15)))
        prompts.append(np.concatenate([shared, tail]).astype(np.int32))
    n_new = 10
    solo = [_greedy_solo(solo_eng, p, n_new) for p in prompts]
    for draft in ("ngram:3", "truncate:1"):
        eng, _ = _engine(slots=4, params=params, prefix_cache=True,
                         draft=draft, spec_k=3)
        sched = ServeScheduler(eng, queue_deadline=0.0)
        done = sched.run([Request(rid=i, prompt=p, max_new_tokens=n_new)
                          for i, p in enumerate(prompts)])
        by = {r.rid: r for r in done}
        for i in range(len(prompts)):
            assert by[i].tokens == solo[i], f"{draft}: request {i} diverged"
        st = sched.stats()
        assert st["prefix"]["hit_rate"] > 0
        assert st["spec"]["proposed"] > 0


def test_spec_eos_and_cap_truncate_accepted_run():
    """EOS or the generation cap inside an accepted run must stop the
    request exactly where sequential decode would."""
    eng, params = _engine(slots=4)
    rng = np.random.default_rng(16)
    prompt = rng.integers(0, 256, 12).astype(np.int32)
    seq = _greedy_solo(eng, prompt, 8)
    spec_eng, _ = _engine(slots=4, params=params, draft="ngram:2",
                          spec_k=4)
    # cap mid-run
    sched = ServeScheduler(spec_eng, queue_deadline=0.0)
    done = sched.run([Request(rid=0, prompt=prompt, max_new_tokens=3)])
    assert done[0].tokens == seq[:3]
    # EOS mid-run: a token whose first occurrence is not at position 0
    # (sequential decode stops at the FIRST occurrence of the EOS token)
    i = next(j for j in range(1, len(seq)) if seq[j] not in seq[:j])
    sched2 = ServeScheduler(spec_eng, queue_deadline=0.0)
    done2 = sched2.run([Request(rid=0, prompt=prompt, max_new_tokens=8,
                                eos_token=int(seq[i]))])
    assert done2[0].tokens == seq[:i + 1]


def test_warm_boot_compile_free_with_spec_and_prefix(tmp_path, monkeypatch):
    """The PR 12 warm-boot contract extended to the hvdspec
    executables: verify, draft and COW-copy all adopt through the
    artifact store's serve kind, so a warm replica with speculation and
    prefix caching on still reaches its first token with builds==0."""
    from horovod_tpu.store import artifact_store
    monkeypatch.setenv("HOROVOD_ARTIFACT_STORE", str(tmp_path / "store"))
    artifact_store.reset_for_tests()
    try:
        cold, params = _engine(prefix_cache=True, draft="truncate:1",
                               spec_k=3)
        # decode + first token + prefill buckets + verify + draft + cow
        assert cold.builds == len(cold.buckets) + 5
        assert {"serve_verify_k3", "serve_draft_l1",
                "serve_cow_copy"} <= set(cold.store_outcomes)
        assert set(cold.store_outcomes.values()) == {"miss"}
        warm, _ = _engine(cfg=cold.cfg, params=params, prefix_cache=True,
                          draft="truncate:1", spec_k=3)
        assert warm.builds == 0
        assert set(warm.store_outcomes.values()) == {"hit"}
    finally:
        artifact_store.reset_for_tests()


def test_pool_gauges_track_allocator():
    from horovod_tpu import metrics as M
    eng, _ = _engine(slots=2, max_seq=64, prefix_cache=True)
    prompt = np.arange(36, dtype=np.int32)                # 3 pages
    slot = eng.reserve(40, prompt=prompt)
    eng.prefill(slot, prompt)
    s2 = eng.reserve(40, prompt=prompt)                   # shares 2 pages
    g_free = M.get_registry().get("hvd_serve_pages_free")
    g_shared = M.get_registry().get("hvd_serve_pages_shared")
    assert g_free is not None and g_shared is not None
    assert g_free.value == eng.allocator.free_pages
    assert g_shared.value == eng.allocator.shared_pages
    assert g_shared.value == 2
    # the /healthz serving block carries the pool view
    h = M.health_snapshot()
    pool = h["serving"]["engine"]["pool"]
    assert pool["free"] == eng.allocator.free_pages
    assert pool["shared"] == 2
    assert 0 < pool["utilization"] <= 1
    eng.release(slot)
    eng.release(s2)


def test_draft_spec_validation_errors():
    with pytest.raises(ValueError, match="truncate needs a layer count"):
        _engine(draft="truncate")
    with pytest.raises(ValueError, match="in \\[1, 1\\]"):
        _engine(draft="truncate:2")
    with pytest.raises(ValueError, match="expected 'off'"):
        _engine(draft="banana")
    with pytest.raises(ValueError, match="HOROVOD_SERVE_SPEC_K"):
        _engine(draft="ngram:3", spec_k=0)


# ---------------------------------------------------------------------------
# one pool, one layout: the flat page ids against per-layer writes
# ---------------------------------------------------------------------------

def _stacked_pool_bodies(cfg):
    """The step bodies over a STACKED pool, the reference the engine's
    flat pool is held to: the one dense block (``tfm.block``, with the
    attentions the engine's bodies use) under an ``attend`` that takes
    layer ``l``'s pool ``[P+1, page, KVH*D]`` as a slice of the scan,
    writes the heads' K and V side by side as one row a token through
    ``write_token_rows`` / ``write_chunk_rows`` (row by row, where the
    engine's prefill writes page by page: ``write_chunk_pages``) with
    plain block tables and its own last page as the scratch page, and
    stacks the results back."""
    from jax import lax
    from horovod_tpu.parallel import tensor_parallel as tp_lib
    scale = cfg.head_dim ** -0.5

    def stacked(params, k_pages, v_pages, tokens, pos, write, attend):
        x = tp_lib.vocab_parallel_embed(
            tokens, params["embed"].astype(cfg.dtype), cfg.tp_axis)

        def layer(x, xs):
            lp, *pages = xs

            def attend_sliced(q, k, v):
                pages[:] = write(pages, tuple(
                    t.reshape(t.shape[0], -1) for t in (k, v)))
                return attend(q, *pages)

            x = tfm.block(cfg, lp, x, pos, attend_sliced,
                          tfm._mlp_half(cfg, lp))
            return x, tuple(pages)

        _, pools = lax.scan(layer, x, (params["layers"], k_pages, v_pages))
        return pools

    def decode(params, k_pages, v_pages, block_tables, lengths, tokens):
        valid = lengths < block_tables.shape[1] * k_pages.shape[2]
        return stacked(
            params, k_pages, v_pages, tokens, lengths,
            lambda pages, new: kvc.write_token_rows(
                pages, new, block_tables, lengths, valid=valid),
            lambda q, kp, vp: kvc.paged_decode_attention(
                q, kp, vp, block_tables, lengths + 1, scale))

    def prefill(params, k_pages, v_pages, block_table, start, n_real,
                tokens):
        pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
        return stacked(
            params, k_pages, v_pages, tokens, pos,
            lambda pages, new: kvc.write_chunk_rows(
                pages, new, block_table, start, n_real),
            lambda q, kp, vp: tfm.attend_gathered(
                q, kp, vp, block_table, pos, scale))

    return decode, prefill


def _pool_engine(tp, **kw):
    """(engine, mesh): three layers, so a stray write has a middle layer
    to land in; over a 2-way TP mesh when ``tp``."""
    mesh = None
    cfg = _cfg(n_layers=3)
    if tp:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        cfg = _cfg(n_layers=3, tp_axis="tp")
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    return ServeEngine(cfg, params, mesh=mesh, slots=4, page=16,
                       max_seq=128, prefill_chunk=64, **kw), mesh


def _assert_pool_in_format(eng):
    for pages in (eng.k_pages, eng.v_pages):
        assert pages.format.layout.major_to_minor == (0, 1, 2, 3)
        assert pages.sharding.is_equivalent_to(eng.pool_format.sharding,
                                               pages.ndim)
        assert pages.committed


@pytest.mark.parametrize("tp", [False, True], ids=["one_device", "tp2"])
def test_flat_pool_equals_per_layer_writes(tp):
    """After chunked prefills (a bucket-padded remainder among them) and
    decode steps with slots empty and mid-prefill, the 4-D pool is
    bitwise what the stacked-pool bodies produce from the same calls:
    every real write in its layer's page, every masked write in THAT
    layer's scratch page (a chunk's padded rows there or nowhere: the
    reference writes them row by row, the engine leaves them out of the
    pages it writes), nothing anywhere else."""
    eng, mesh = _pool_engine(tp)
    decode, prefill = _stacked_pool_bodies(eng.cfg)
    if tp:
        from jax.sharding import PartitionSpec as P
        from horovod_tpu.eager import shard_map
        kv, rep = eng.pool_format.sharding.spec, P()
        pspecs = tfm.param_specs(eng.cfg)
        decode = shard_map(decode, mesh, (pspecs, kv, kv, rep, rep, rep),
                           (kv, kv))
        prefill = shard_map(prefill, mesh,
                            (pspecs, kv, kv, rep, rep, rep, rep), (kv, kv))
    decode, prefill = jax.jit(decode), jax.jit(prefill)
    want = [jnp.zeros_like(eng.k_pages), jnp.zeros_like(eng.v_pages)]

    def shadow(program, reference):
        def call(params, k, v, *rest):
            want[:] = reference(params, *want, *rest)
            return program(params, k, v, *rest)
        return call

    eng._decode = shadow(eng._decode, decode)
    eng._prefill = {b: shadow(fn, prefill)
                    for b, fn in eng._prefill.items()}

    rng = np.random.default_rng(21)
    long = rng.integers(0, 256, 70).astype(np.int32)   # 64 + 6 padded to 32
    short = rng.integers(0, 256, 20).astype(np.int32)
    a = eng.reserve(long.size + 8)
    b = eng.reserve(short.size + 8)
    tokens = np.zeros((eng.slots,), np.int32)
    tokens[b] = eng.prefill(b, short)
    start, first = eng.prefill_chunk(a, long, 0)        # a is mid-prefill
    assert first is None
    for _ in range(3):              # b decodes; a and two slots masked
        tokens[b] = eng.decode_step(tokens)[b]
    _, tokens[a] = eng.prefill_chunk(a, long, start)
    for _ in range(3):
        nxt = eng.decode_step(tokens)
        tokens[a], tokens[b] = nxt[a], nxt[b]

    for got, ref in zip((eng.k_pages, eng.v_pages), want):
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.shape == (3, eng.pool.n_pages + 1, 16, 4 * 16)
        scratch = eng.pool.scratch_page
        np.testing.assert_array_equal(got[:, :scratch], ref[:, :scratch])
        held = set(eng.slot_pages[a]) | set(eng.slot_pages[b])
        for layer in range(3):
            assert np.any(got[layer, scratch])      # its own scratch page
            for page in range(eng.pool.n_pages):
                if page not in held:
                    assert not np.any(got[layer, page]), (layer, page)
    _assert_pool_in_format(eng)


def test_truncated_draft_leaves_deeper_layers_untouched():
    eng, _ = _pool_engine(False, draft="truncate:1", spec_k=2)
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, 256, 20).astype(np.int32)
    slot = eng.reserve(prompt.size + 8)
    tokens = np.zeros((eng.slots,), np.int32)
    tokens[slot] = eng.prefill(slot, prompt)
    before = np.asarray(eng.k_pages), np.asarray(eng.v_pages)
    active = np.zeros((eng.slots,), bool)
    active[slot] = True
    eng.propose_drafts(tokens, active)
    for was, now in zip(before, (eng.k_pages, eng.v_pages)):
        now = np.asarray(now)
        np.testing.assert_array_equal(now[1:], was[1:])
        page = eng.slot_pages[slot][prompt.size // 16]
        assert np.any(now[0, page] != was[0, page])     # layer 0 drafted
    _assert_pool_in_format(eng)


@pytest.mark.parametrize("tp", [False, True], ids=["one_device", "tp2"])
def test_every_program_returns_the_pool_in_the_engines_format(tp):
    """Decode, prefill, verify, draft and COW all hand the pool back in
    the engine's one Format (layout, sharding, committed); none falls
    back to the jit path, which a rejected layout or sharding would."""
    eng, _ = _pool_engine(tp, prefix_cache=True, draft="truncate:1",
                          spec_k=2)
    _assert_pool_in_format(eng)                         # as allocated
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 256, 40).astype(np.int32)
    slot = eng.reserve(prompt.size + 8, prompt=prompt)
    tokens = np.zeros((eng.slots,), np.int32)
    tokens[slot] = eng.prefill(slot, prompt)
    _assert_pool_in_format(eng)                         # prefill
    tokens[slot] = eng.decode_step(tokens)[slot]
    _assert_pool_in_format(eng)                         # decode
    active = np.zeros((eng.slots,), bool)
    active[slot] = True
    drafts = eng.propose_drafts(tokens, active)
    _assert_pool_in_format(eng)                         # draft
    eng.spec_step(tokens, drafts, active=active)
    _assert_pool_in_format(eng)                         # verify
    diverging = prompt.copy()
    diverging[20] = (diverging[20] + 1) % 256           # inside page 1
    assert eng.reserve(diverging.size + 8, prompt=diverging) is not None
    assert eng.cow_copies == 1
    _assert_pool_in_format(eng)                         # COW
    assert eng.stats()["store_rejected"] == []
    assert set(eng.program_temp_bytes) == set(eng.store_outcomes)


def test_engine_build_lowers_from_shapes_and_holds_one_pool(monkeypatch):
    """Engine build hands the compiler shapes, never arrays (a donated
    example argument would be a second pool), and leaves no array of the
    pool's size alive beside K and V."""
    from horovod_tpu.store import artifact_store
    cfg = _cfg()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lowered_from = []
    adopt = artifact_store.adopt_step

    def spy(fn, args, **kw):
        lowered_from.extend(jax.tree.leaves(args))
        return adopt(fn, args, **kw)

    monkeypatch.setattr(artifact_store, "adopt_step", spy)
    before = {id(x) for x in jax.live_arrays()}
    eng, _ = _engine(cfg, params, prefix_cache=True, draft="truncate:1",
                     spec_k=2)
    assert lowered_from and all(
        isinstance(x, jax.ShapeDtypeStruct) for x in lowered_from)
    pool_sized = [x for x in jax.live_arrays() if id(x) not in before
                  and x.nbytes >= eng.k_pages.nbytes]
    assert {id(x) for x in pool_sized} == {id(eng.k_pages),
                                           id(eng.v_pages)}


def test_engine_compiles_in_process_where_a_reload_loses_the_layout(
        monkeypatch):
    """libtpu 0.0.34 hands a reloaded executable's results back in the
    default layout: where the probe says so, the engine neither takes
    its programs from the artifact store nor from JAX's persistent
    cache, and serves the same tokens."""
    from horovod_tpu.store import artifact_store
    from horovod_tpu.utils import compile_cache
    cfg = _cfg()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(24).integers(0, 256, 40).astype(np.int32)
    want = _greedy_solo(_engine(cfg, params)[0], prompt, 5)

    monkeypatch.setattr(artifact_store, "reload_keeps_layout",
                        lambda fmt, shape, dtype: False)
    monkeypatch.setattr(
        artifact_store, "adopt_step",
        lambda *a, **kw: pytest.fail("a program came through the store"))
    uncached, entered = compile_cache.uncached, []

    def counting():
        entered.append(1)
        return uncached()

    monkeypatch.setattr(compile_cache, "uncached", counting)
    eng, _ = _engine(cfg, params, prefix_cache=True)
    assert set(eng.store_outcomes.values()) == {"unsupported"}
    assert eng.builds == len(eng.store_outcomes) and len(entered) == 1
    assert jax.config.jax_enable_compilation_cache      # restored
    assert _greedy_solo(eng, prompt, 5) == want


@pytest.mark.parametrize("tp", [False, True], ids=["one_device", "tp2"])
def test_reload_probe_runs_on_one_device_of_the_pools_sharding(tp):
    """On the CPU backend a reload keeps the pinned layout (row-major is
    the only one); the probe itself must run for a sharded pool too."""
    from horovod_tpu.store import artifact_store
    eng, _ = _pool_engine(tp)
    assert eng.reload_keeps_layout is True
    assert artifact_store.reload_keeps_layout(
        eng.pool_format, (1, 1, 16, 2 * 16), jnp.bfloat16) is True


@pytest.mark.parametrize("start,n_real,c", [
    (0, 64, 64),          # aligned, whole pages
    (0, 6, 32),           # a bucket-padded remainder
    (16, 40, 64),         # aligned start, padding inside its last page
    (21, 64, 64),         # unaligned (a copy-on-write prefix): 5 pages
    (37, 1, 32),          # one real row
    (96, 32, 64),         # the table's last pages, the window past its end
])
def test_chunk_written_by_pages_is_the_chunk_written_by_rows(start, n_real,
                                                             c):
    """``write_chunk_pages`` (read the chunk's pages, lay the real rows
    over them, write them back whole) leaves every page but the scratch
    page as ``write_chunk_rows`` (one scatter a row) does, wherever the
    chunk starts and however much of it is padding, for both arrays of a
    block; rows it does not write keep what they held."""
    rng = np.random.default_rng(27)
    page, n_max, row = 16, 8, 24
    pools = tuple(jnp.asarray(rng.standard_normal((n_max + 3, page, row)),
                              jnp.float32) for _ in range(2))
    new = tuple(jnp.asarray(rng.standard_normal((c, row)), jnp.float32)
                for _ in range(2))
    table = jnp.asarray(rng.permutation(n_max + 2)[:n_max], jnp.int32)
    args = (table, jnp.int32(start), jnp.int32(n_real))
    by_rows = jax.jit(kvc.write_chunk_rows)(pools, new, *args)
    by_pages = jax.jit(kvc.write_chunk_pages)(pools, new, *args)
    for was, want, got in zip(pools, by_rows, by_pages):
        np.testing.assert_array_equal(np.asarray(got)[:-1],
                                      np.asarray(want)[:-1])
        # the scratch page got its own rows back
        np.testing.assert_array_equal(np.asarray(got)[-1],
                                      np.asarray(was)[-1])
        pos = np.arange(start, start + n_real)
        np.testing.assert_array_equal(
            np.asarray(got)[np.asarray(table)[pos // page], pos % page],
            np.asarray(new[0] if was is pools[0] else new[1])[:n_real])


# ---------------------------------------------------------------------------
# the dense row: a token's KV heads side by side
# ---------------------------------------------------------------------------

def test_dense_rows_is_one_row_a_token_that_tp_splits_by_whole_heads():
    """``dense_rows`` describes K and V as ONE row of ``KVH * D`` numbers a
    token and layer (the last axis fills the lanes whatever ``D`` is), and
    tensor parallelism splits that row into whole KV heads in head order:
    a 2-way engine's pool, shard beside shard, is the one-device pool."""
    rows = kvc.dense_rows(3, 4, 16)
    assert [(r.name, r.blocks, r.row, r.tp_axis) for r in rows] == [
        ("k", 3, (64,), 0), ("v", 3, (64,), 0)]
    pool = kvc.PagePool(3, 8, 16, 4, 16)
    assert pool.rows == rows
    assert pool.shapes() == ((3, 9, 16, 64),) * 2
    assert pool.nbytes() == 2 * 3 * 9 * 16 * 64 * 4

    one, _ = _pool_engine(False)
    two, _ = _pool_engine(True)
    prompt = np.random.default_rng(25).integers(0, 256, 40).astype(np.int32)
    assert _greedy_solo(one, prompt, 4) == _greedy_solo(two, prompt, 4)
    for whole, split in zip(one.pools, two.pools):
        shards = sorted(split.addressable_shards, key=lambda s: s.index[3])
        assert [s.data.shape for s in shards] == [(3, 33, 16, 2 * 16)] * 2
        assert [s.index[3] for s in shards] == [slice(0, 32), slice(32, 64)]
        # page 0 is not handed out first (the free list is LIFO from the
        # top), so compare every page but each layer's scratch
        np.testing.assert_allclose(np.asarray(split)[:, :-1],
                                   np.asarray(whole)[:, :-1],
                                   rtol=1e-4, atol=1e-5)
        assert np.any(np.asarray(whole)[:, :-1, :, 32:])    # heads 2, 3


def test_engine_through_the_kernel_serves_the_reference_paths_tokens():
    """The decode program with the paged-decode kernel in it (interpret
    mode on the CPU, over the float32 pool's flat row) serves the tokens
    the same engine serves through the jnp reference, prompt chunks,
    ragged slots and all."""
    from horovod_tpu.config import knobs
    cfg = _cfg()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(26)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (70, 9, 33)]

    def served():
        eng, _ = _engine(cfg, params)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        done = ServeScheduler(eng).run(reqs)
        return {r.rid: list(r.tokens) for r in done}

    want = served()
    knobs.set_override("HOROVOD_TPU_PALLAS", "interpret")
    try:
        assert fa.enabled() == "interpret"
        assert served() == want
    finally:
        knobs.clear_override("HOROVOD_TPU_PALLAS")
    assert len(want) == 3 and all(len(t) == 6 for t in want.values())


# ---------------------------------------------------------------------------
# layering: the engine holds no model, no model imports the engine
# ---------------------------------------------------------------------------

_PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "horovod_tpu")


def test_models_do_not_reach_into_the_engine():
    """In a fresh interpreter ``import horovod_tpu.models`` loads nothing
    of ``horovod_tpu.serving``, and no source file under ``models/`` names
    ``serving.engine``: a model builds its ``ServeModel`` from
    ``serving.model`` and ``serving.kv_cache``, inside functions."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, horovod_tpu.models\n"
         "print(sorted(m for m in sys.modules "
         "if m.startswith('horovod_tpu.serving')))"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
    models = os.path.join(_PACKAGE, "models")
    for name in sorted(os.listdir(models)):
        if name.endswith(".py"):
            with open(os.path.join(models, name)) as f:
                assert "serving.engine" not in f.read(), name


def test_the_engine_names_no_leaf_and_no_scope():
    with open(os.path.join(_PACKAGE, "serving", "engine.py")) as f:
        source = f.read()
    for word in ("named_scope(", '"wq"', '"w_in"', '"attn_norm"',
                 "tensor_parallel", "TransformerConfig)"):
        assert word not in source, word


@pytest.mark.parametrize("family", ["dense", "longcat", "none"])
def test_serve_model_asks_the_config(family):
    from horovod_tpu.models import LongCatFlashConfig
    from horovod_tpu.serving import engine as eng_mod
    if family == "none":
        with pytest.raises(TypeError, match="serve_model"):
            eng_mod.serve_model(object())
        return
    cfg = _cfg() if family == "dense" else LongCatFlashConfig()
    model = eng_mod.serve_model(cfg)
    assert isinstance(model, eng_mod.ServeModel)
    assert len(model.cache_rows(cfg)) == (2 if family == "dense" else 1)
    assert (model.draft is not None) == (family == "dense")
    # the names the benchmark's compile check and bench.py import
    assert eng_mod._decode_body is tfm.decode_body
    assert eng_mod._prefill_body is tfm.prefill_body
    assert eng_mod.cast_once(jnp.ones((2,), jnp.float32),
                             jnp.bfloat16).dtype == jnp.bfloat16
