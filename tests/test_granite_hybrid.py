"""The hybrid state-space / attention model (``models/granite_hybrid.py``)
through ``ServeEngine`` and ``ServeScheduler`` at a small size, against the
plain reference (``benchmarks/reference/granite_hybrid.py``, which imports
nothing of the program and runs the recurrence token by token): slot state
beside the paged pool, the chunked scan, the share of the routed experts,
the second gate rule of ``parallel/moe.py``, what the engine refuses for a
model with slot state, and the scopes in the compiled programs."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as M
from horovod_tpu.models import granite_hybrid as gh
from horovod_tpu.parallel import moe
from horovod_tpu.serving import Request, ServeEngine, ServeScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import lowprec                          # noqa: E402
from benchmarks.reference import granite_hybrid as ref      # noqa: E402

SMALL = dict(vocab_size=128, d_model=64,
             layer_types=("mamba", "mamba", "attention", "mamba"),
             n_heads=4, n_kv_heads=2, attention_multiplier=0.25,
             mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
             mamba_chunk_size=16, n_routed_experts=8, top_k=3, d_expert=32,
             d_shared=48, max_seq=128)


def _cfg(**kw):
    return gh.GraniteHybridConfig(**{**SMALL, "dtype": jnp.float32, **kw})


def _dims(cfg):
    return ref.Dims(
        layer_types=cfg.layer_types, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads,
        attention_multiplier=cfg.attention_multiplier,
        ssm_heads=cfg.mamba_n_heads, ssm_head=cfg.mamba_d_head,
        ssm_state=cfg.mamba_d_state, conv=cfg.mamba_d_conv,
        n_routed=cfg.n_routed_experts, top_k=cfg.top_k,
        first=cfg.expert_first, count=cfg.held_experts,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling, eps=cfg.norm_eps)


def _params(cfg, seed=1):
    """Seeded weights with every norm scale, the convolution's bias and the
    skip off their neutral values, so none of them can be dropped unseen;
    the embedding four times its draw, so the logits are no flat line."""
    params = gh.init_params(cfg, jax.random.PRNGKey(seed))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(flat))
    out = []
    for (path, a), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        if name == "['embed']":
            a = a * 4
        elif "norm" in name or name.endswith(("['conv_b']", "['D']")):
            a = a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


def _reference_logits(cfg, params, tokens):
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            lowprec.F32, _dims(cfg), wide, jnp.asarray(tokens),
            jnp.arange(len(tokens))))


def _engine(cfg, params, **kw):
    kw = {"slots": 3, "page": 8, "max_seq": 128, "prefill_chunk": 64,
          "prefix_cache": False, "draft": "off", **kw}
    return ServeEngine(cfg, params, None, **kw)


def _chunk(eng, slot, prompt, start):
    """One prefill chunk as ``ServeEngine.prefill_chunk`` runs it, keeping
    its logits: (the next start, the row the logits are of, the greedy
    token, the logits)."""
    n_real = min(len(prompt) - start, eng.bucket_for(len(prompt) - start))
    bucket = eng.bucket_for(n_real)
    chunk = np.zeros((bucket,), np.int32)
    chunk[:n_real] = prompt[start:start + n_real]
    tok, logits = eng._step(
        eng._prefill[bucket], jnp.asarray(eng.tables.tables[slot].copy()),
        *eng._slot_arg(slot), jnp.asarray(start, jnp.int32),
        jnp.asarray(n_real, jnp.int32), jnp.asarray(chunk))
    start += n_real
    if start == len(prompt):
        eng.tables.lengths[slot] = len(prompt)
    return start, start - 1, int(tok), np.asarray(logits)


def _decode(eng, tokens):
    """One decode step over the slots whose prompt is cached (a slot
    mid-prefill shows length 0, as ``decode_step`` presents it)."""
    active = eng.tables.lengths > 0
    bt, ln = eng.tables.device_views(active)
    nxt, logits = eng._step(
        eng._decode, bt, ln, jnp.asarray(np.asarray(tokens, np.int32)))
    nxt, logits = np.asarray(nxt), np.asarray(logits)
    eng.tables.lengths[active] += 1
    return nxt, logits


def _interleaved(cfg, params):
    """Two requests through one engine, program by program: B's prompt of 21
    tokens (one padded chunk), then A's of 70 in two chunks (a full bucket
    of 64, then 6 padded to 32) WITH a decode step of B between them, then
    four decode steps of both. Returns for each request its sequence and
    the logits the engine gave, by row."""
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    a_prompt = rng.integers(0, cfg.vocab_size, 70).astype(np.int32)
    b_prompt = rng.integers(0, cfg.vocab_size, 21).astype(np.int32)
    b, a = eng.reserve(40), eng.reserve(90)
    seq = {a: list(a_prompt), b: list(b_prompt)}
    got = {a: {}, b: {}}
    token = np.zeros((eng.slots,), np.int32)

    def chunk(slot, prompt, start):
        start, row, tok, lg = _chunk(eng, slot, prompt, start)
        got[slot][row] = lg
        token[slot] = tok
        return start

    def decode():
        live = np.flatnonzero(eng.tables.lengths > 0)
        for s in live:
            seq[s].append(int(token[s]))
        nxt, lg = _decode(eng, token)
        for s in live:
            got[s][len(seq[s]) - 1] = lg[s]
            token[s] = nxt[s]

    assert chunk(b, b_prompt, 0) == 21
    assert chunk(a, a_prompt, 0) == 64
    decode()                            # B alone; A is mid-prefill
    assert chunk(a, a_prompt, 64) == 70
    for _ in range(4):
        decode()
    return eng, [(seq[s], got[s]) for s in (a, b)]


def _check(cfg, params, served, atol, rtol=0.0):
    for seq, got in served:
        want = _reference_logits(cfg, params, np.array(seq, np.int32))
        assert len(got) >= 5
        for row, lg in got.items():
            np.testing.assert_allclose(lg, want[row], atol=atol, rtol=rtol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_engine_prefill_and_decode_through_pages_and_slot_state_match_the_reference(dtype):
    """Chunked prefill (a full bucket, then a padded one of another size,
    scan chunks of 16 inside both) carrying the recurrent state from chunk
    to chunk while another slot decodes, then decode: logits against the
    reference's one full pass with its token-by-token recurrence. The share
    held is experts 2..5 of 8."""
    cfg = _cfg(expert_first=2, expert_count=4, dtype=dtype)
    params = gh.init_params(cfg, jax.random.PRNGKey(3))
    params = jax.tree.map(lambda a, b: b.astype(a.dtype), params,
                          _params(_cfg(expert_first=2, expert_count=4), 3))
    eng, served = _interleaved(cfg, params)
    if dtype == jnp.float32:
        _check(cfg, params, served, atol=2e-4, rtol=2e-4)
    else:       # bfloat16 products: a few hundredths of the logits' spread
        spread = float(np.std(_reference_logits(
            cfg, params, np.array(served[0][0], np.int32))))
        _check(cfg, params, served, atol=0.15 * spread)
    # two kinds of cache side by side: pages for the one attention layer,
    # a state a slot for the three Mamba layers
    assert [p.shape for p in eng.pools] == [
        (1, eng.pool.n_pages + 1, 8, cfg.n_kv_heads * cfg.head_dim)] * 2
    conv, ssm = eng.state[-2:]
    assert conv.shape == (3, cfg.mamba_d_conv - 1, eng.slots, cfg.conv_dim)
    assert ssm.shape == (3, eng.slots, cfg.mamba_d_state, cfg.d_inner)
    assert conv.dtype == ssm.dtype == jnp.float32
    s = eng.stats()["ssm"]
    assert s == {"state_bytes": conv.nbytes + ssm.nbytes, "slots": 3,
                 "layers": 3, "resets": 2, "chunks_carried": 1,
                 "decode_rows": 1 + 2 * 4}
    assert M.get_registry().get("hvd_serve_ssm_chunks_carried").value == 1
    assert M.get_registry().get("hvd_serve_ssm_state_bytes").value \
        == s["state_bytes"]
    held = eng.stats()["moe"]
    rows = 21 + 70 + 9
    assert (held["assignments_held"] + held["assignments_absent"]
            == rows * cfg.top_k * cfg.n_layers)
    assert held["assignments_zero"] == 0


def _faulty(monkeypatch, fault):
    if fault == "scan_skips_the_carried_state":
        sound = gh.ssm_chunk_scan
        monkeypatch.setattr(
            gh, "ssm_chunk_scan", lambda x, step, a, b, c, d, s, chunk:
            sound(x, step, a, b, c, d, jnp.zeros_like(s), chunk))
    elif fault == "conv_tail_from_padded_rows":
        monkeypatch.setattr(gh, "conv_tail",
                            lambda window, n_real, k1: window[-k1:])
    elif fault == "decode_advances_a_slot_mid_prefill":
        sound = gh.mamba_decode
        monkeypatch.setattr(
            gh, "mamba_decode", lambda cfg, mp, u, conv, ssm, layer, live:
            sound(cfg, mp, u, conv, ssm, layer, jnp.ones_like(live)))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["scan_skips_the_carried_state",
                                   "conv_tail_from_padded_rows",
                                   "decode_advances_a_slot_mid_prefill"])
def test_a_fault_in_the_slot_state_fails_the_comparison(monkeypatch, fault):
    """Each of the three ways to lose the state that the comparison has to
    see: the same drive as the sound test, the program with the fault."""
    cfg = _cfg()
    params = _params(cfg, 3)
    _faulty(monkeypatch, fault)
    _, served = _interleaved(cfg, params)
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        _check(cfg, params, served, atol=2e-4, rtol=2e-4)


def _scan_inputs(cfg, rows, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    x = jax.random.normal(ks[0], (rows, h, p))
    step = jax.nn.softplus(jax.random.normal(ks[1], (rows, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (rows, n))
    c = jax.random.normal(ks[4], (rows, n))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x, step, a, b, c, d


def _token_by_token(x, step, a, b, c, d, s):
    """``ssm_step`` a row at a time (the decode path's recurrence)."""
    ys = []
    for t in range(x.shape[0]):
        y, s = gh.ssm_step(x[t:t + 1], step[t:t + 1], a, b[t:t + 1],
                           c[t:t + 1], d, s[None])
        ys.append(y[0])
        s = s[0]
    return jnp.stack(ys), s


@pytest.mark.parametrize("carried", [False, True], ids=["zeros", "carried"])
@pytest.mark.parametrize("rows, real", [(16, 16), (64, 64), (64, 41),
                                        (32, 2)],
                         ids=["one_chunk", "four_chunks", "padded",
                              "two_real_rows"])
def test_the_chunked_scan_is_the_recurrence_token_by_token(carried, rows,
                                                           real):
    """Prefill's chunked form against decode's one-step form over the real
    rows, and against the reference's own recurrence; a padded row (step 0)
    moves neither the outputs before it nor the state."""
    cfg = _cfg()
    x, step, a, b, c, d = _scan_inputs(cfg, rows)
    step = step * (jnp.arange(rows) < real)[:, None]
    s0 = (jax.random.normal(jax.random.PRNGKey(9),
                            (cfg.mamba_d_state, cfg.d_inner))
          if carried else jnp.zeros((cfg.mamba_d_state, cfg.d_inner)))
    with jax.default_matmul_precision("highest"):
        y, s = gh.ssm_chunk_scan(x, step, a, b, c, d, s0,
                                 cfg.mamba_chunk_size)
    want_y, want_s = _token_by_token(x[:real], step[:real], a, b[:real],
                                     c[:real], d, s0)
    np.testing.assert_allclose(y[:real], want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)
    if not carried:
        np.testing.assert_allclose(
            y[:real], ref.recurrence(lowprec.F32, x[:real], step[:real], a,
                                     b[:real],
                                     c[:real], d), atol=2e-5, rtol=2e-5)


def test_a_padded_chunk_stores_the_tail_of_its_last_real_rows():
    window = jnp.arange(3 + 8, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
    for n_real, want in ((8, [8, 9, 10]), (5, [5, 6, 7]), (1, [1, 2, 3])):
        got = gh.conv_tail(window, jnp.asarray(n_real), 3)
        assert got[:, 0].tolist() == want


# ---------------------------------------------------------------------------
# the share of the routed experts, and the second gate rule
# ---------------------------------------------------------------------------

def _moe_inputs(cfg, rows=10, seed=3):
    ep = jax.tree.map(lambda a: a[0], _params(cfg, seed)["layers"]["moe"])
    return ep, jax.random.normal(jax.random.PRNGKey(seed), (rows, cfg.d_model))


def _program_moe(cfg, ep, h, first, count, shared=True):
    """``experts`` on the share, less the residual it adds to."""
    sl = slice(first, first + count)
    share = {**ep, **{k: ep[k][sl] for k in ("w_gate", "w_up", "w_down")}}
    if not shared:
        share["shared"] = jax.tree.map(jnp.zeros_like, ep["shared"])
    cut = dataclasses.replace(cfg, expert_first=first, expert_count=count)
    with jax.default_matmul_precision("highest"):
        out, _ = gh.experts(cut, share, h, None)
    return np.asarray(out - h) / cfg.residual_multiplier


def _reference_moe(cfg, ep, h, first, count, shared=True):
    dims = dataclasses.replace(_dims(cfg), first=first, count=count)
    sl = slice(first, first + count)
    share = {**ep, **{k: ep[k][sl] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.moe(
            lowprec.F32, dims, ref.rmsnorm(h, ep["norm"], cfg.norm_eps),
            share, shared=shared))


@pytest.mark.parametrize("layer_of", [_program_moe, _reference_moe],
                         ids=["program", "reference"])
@pytest.mark.parametrize("count", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(layer_of, count):
    """Guide section 4: the routed parts of all n / count shares (8 experts
    as 2 x 4, or 4 x 2) plus the shared expert counted once = the layer with
    every expert held."""
    cfg = _cfg()
    ep, h = _moe_inputs(cfg)
    n = cfg.n_routed_experts
    whole = layer_of(cfg, ep, h, 0, n)
    routed = sum(layer_of(cfg, ep, h, first, count, shared=False)
                 for first in range(0, n, count))
    shared_once = (layer_of(cfg, ep, h, 0, count)
                   - layer_of(cfg, ep, h, 0, count, shared=False))
    assert np.abs(shared_once).max() > 0.01 and np.abs(routed).max() > 0.01
    np.testing.assert_allclose(routed + shared_once, whole, atol=2e-5,
                               rtol=2e-5)
    # and the program's share is the reference's
    np.testing.assert_allclose(_program_moe(cfg, ep, h, count, count),
                               _reference_moe(cfg, ep, h, count, count),
                               atol=2e-5, rtol=2e-5)


def test_softmax_over_the_chosen_gates_sum_to_one_and_topk_route_is_untouched():
    cfg = _cfg()
    ep, u = _moe_inputs(cfg, rows=12)
    r = moe.topk_softmax_route(u, ep["router"], cfg.top_k)
    assert r.experts.shape == r.gates.shape == (12, cfg.top_k)
    np.testing.assert_allclose(np.sum(r.gates, axis=-1), 1.0, atol=1e-6)
    logits = np.asarray(jnp.dot(u, ep["router"], precision="highest"))
    for t in range(12):
        top = np.argsort(-logits[t])[:cfg.top_k]
        assert sorted(r.experts[t].tolist()) == sorted(top.tolist())
        e = np.exp(logits[t, top] - logits[t, top].max())
        np.testing.assert_allclose(
            np.asarray(r.gates[t])[np.argsort(-logits[t, r.experts[t]])],
            e / e.sum(), atol=1e-6)
    # the first rule: softmax over every output, not renormalised
    old = moe.topk_route(u, ep["router"], jnp.zeros((8,)), cfg.top_k, 2.0)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(
        old.gates, 2.0 * np.take_along_axis(p, np.asarray(old.experts), -1),
        atol=1e-6)
    assert np.all(np.sum(old.gates, axis=-1) < 2.0)
    assert np.array_equal(np.sort(old.experts, -1), np.sort(r.experts, -1))


# ---------------------------------------------------------------------------
# slots and their state in the engine
# ---------------------------------------------------------------------------

def _serve_one(eng, prompt, n_out):
    slot = eng.reserve(len(prompt) + n_out)
    start, logits = 0, []
    while start < len(prompt):
        start, _, tok, lg = _chunk(eng, slot, prompt, start)
    logits.append(lg)
    token = np.zeros((eng.slots,), np.int32)
    for _ in range(n_out):
        token[slot] = tok
        nxt, lg = _decode(eng, token)
        tok = int(nxt[slot])
        logits.append(lg[slot])
    eng.release(slot)
    return slot, np.stack(logits)


def test_a_slot_reused_by_a_second_request_gives_a_fresh_engines_logits():
    """Nobody clears a released slot's state: a prompt's first chunk starts
    from zeros whatever the slot holds."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(4)
    first = rng.integers(0, cfg.vocab_size, 50).astype(np.int32)
    second = rng.integers(0, cfg.vocab_size, 37).astype(np.int32)
    eng = _engine(cfg, params, slots=1)
    slot, _ = _serve_one(eng, first, 3)
    assert float(jnp.abs(eng.state[-1][:, slot]).max()) > 0     # left behind
    again, reused = _serve_one(eng, second, 3)
    assert again == slot
    _, fresh = _serve_one(_engine(cfg, params, slots=1), second, 3)
    np.testing.assert_array_equal(reused, fresh)


def test_a_decode_step_between_two_chunks_leaves_the_prefilling_slots_state_bit_equal():
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, params)
    rng = np.random.default_rng(6)
    a_prompt = rng.integers(0, cfg.vocab_size, 70).astype(np.int32)
    b_prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    b, a = eng.reserve(20), eng.reserve(80)
    _, _, tok, _ = _chunk(eng, b, b_prompt, 0)
    start, *_ = _chunk(eng, a, a_prompt, 0)
    assert start == 64 and eng.tables.lengths[a] == 0
    before = [np.asarray(s) for s in eng.state[-2:]]
    token = np.full((eng.slots,), 5, np.int32)
    token[b] = tok
    _decode(eng, token)
    conv, ssm = (np.asarray(s) for s in eng.state[-2:])
    np.testing.assert_array_equal(conv[:, :, a], before[0][:, :, a])
    np.testing.assert_array_equal(ssm[:, a], before[1][:, a])
    assert np.abs(ssm[:, a]).max() > 0
    # the decoding slot's state moved, the empty slot's did not
    assert not np.array_equal(ssm[:, b], before[1][:, b])
    free = ({0, 1, 2} - {a, b}).pop()
    np.testing.assert_array_equal(ssm[:, free], before[1][:, free])
    np.testing.assert_array_equal(conv[:, :, free], before[0][:, :, free])


def _requests(cfg, sizes, n_out, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=rid, prompt=rng.integers(
        0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=n_out)
        for rid, n in enumerate(sizes)]


def test_scheduler_run_with_decode_ahead_gives_the_direct_loops_tokens():
    """``ServeScheduler`` unchanged, no branch for this model: more requests
    than slots through admission, chunked prefill interleaved with batched
    decode and slot turnover, each step queued before the last is read,
    against the reference's greedy continuation and against the direct
    loop (``engine.prefill`` then ``decode_step(tokens)``)."""
    cfg = _cfg(expert_first=4, expert_count=4)
    params = _params(cfg, seed=2)
    sizes, n_out = (5, 70, 19, 40, 9, 66), 5
    eng = _engine(cfg, params)
    done = ServeScheduler(eng).run(_requests(cfg, sizes, n_out))
    assert len(done) == len(sizes)
    counts = eng.stats()["decode"]
    assert counts["dispatched_ahead"] >= counts["steps"] - 3
    direct = _engine(cfg, params, slots=1)
    for req in sorted(done, key=lambda r: r.rid):
        assert req.error is None and len(req.tokens) == n_out
        seq = list(req.prompt) + list(req.tokens)
        want = _reference_logits(cfg, params, np.array(seq[:-1], np.int32))
        n = len(req.prompt)
        assert list(req.tokens) == [
            int(np.argmax(want[n - 1 + i])) for i in range(n_out)]
        slot = direct.reserve(n + n_out)
        toks = [direct.prefill(slot, req.prompt)]
        for _ in range(n_out - 1):
            feed = np.zeros((1,), np.int32)
            feed[slot] = toks[-1]
            toks.append(int(direct.decode_step(feed)[slot]))
        direct.release(slot)
        assert toks == list(req.tokens)
    s = eng.stats()["ssm"]
    assert s["resets"] == len(sizes) and s["chunks_carried"] == 2


@pytest.mark.parametrize("kw, reason", [
    ({"prefix_cache": True}, "skip prompt tokens the recurrent layers"),
    ({"draft": "ngram:2", "spec_k": 2}, "plain decode only"),
    ({"draft": "truncate:1", "spec_k": 2}, "plain decode only"),
], ids=["prefix_cache", "ngram", "truncate"])
def test_what_slot_state_cannot_give_is_refused_with_the_reason(kw, reason):
    cfg = _cfg()
    with pytest.raises(ValueError, match=reason):
        _engine(cfg, _params(cfg), **kw)


def test_rollback_is_refused_with_the_reason():
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg), slots=1)
    slot = eng.reserve(20)
    eng.prefill(slot, np.arange(9, dtype=np.int32))
    with pytest.raises(ValueError, match="cannot give it back"):
        eng.rollback(slot, 1)


def test_a_config_the_bodies_do_not_serve_is_refused():
    for kw, reason in (({"mamba_n_groups": 2}, "one group of B and C"),
                       ({"layer_types": ("mamba",) * 3}, "at least one"),
                       ({"expert_first": 6, "expert_count": 4},
                        "does not lie")):
        cfg = _cfg(**kw)
        with pytest.raises(ValueError, match=reason):
            cfg.serve_model().check(cfg, "off")


def test_layers_of_two_kinds_are_scanned_in_runs():
    cfg = _cfg(layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    assert cfg.runs() == [("mamba", 0, 0, 5), ("attention", 5, 0, 1),
                          ("mamba", 6, 5, 4)]
    assert gh.GraniteHybridConfig().layer_types.count("attention") == 4
    assert [i for i, k in enumerate(gh.GraniteHybridConfig().layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    shapes = gh.param_shapes(cfg)["layers"]
    assert shapes["mamba"]["w_in"][0][0] == 9
    assert shapes["attention"]["wq"][0][0] == 1
    assert shapes["moe"]["router"][0][0] == 10


# ---------------------------------------------------------------------------
# scopes in the compiled programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def texts():
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg))
    return {label: eng.executable_text(label)
            for label in ("serve_decode", "serve_prefill_32",
                          "serve_prefill_64")}


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_32",
                                     "serve_prefill_64"])
def test_the_scopes_are_in_the_compiled_programs(texts, program):
    names = set(re.findall(r'op_name="([^"]*)"', texts[program]))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("hvd_ssm_proj", "hvd_ssm_conv", "hvd_ssm_scan",
                  "hvd_ssm_gate"):
        assert some("hvd_ssm/" + scope), scope
    for scope in ("hvd_moe_router", "hvd_moe_experts", "hvd_moe_combine"):
        assert some("hvd_moe/" + scope), scope
    for scope in ("hvd_attention", "hvd_kv_write", "hvd_mlp"):
        assert some(scope), scope
    assert texts[program].splitlines()[0].startswith(
        "HloModule jit_hvd_serve_" + program.split("_")[1])
    # the state's read and write stand under the scan's scope
    assert some("hvd_ssm_scan", "dynamic_update_slice")
    assert some("hvd_ssm_conv", "dynamic_update_slice")


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_32"])
def test_no_norm_stands_under_hvd_mlp(texts, program):
    """The shared expert stands under ``hvd_mlp``, the norm before the
    expert block outside it (and outside ``hvd_moe``), as the dense block's
    does; the gated norm of a Mamba layer is ``hvd_ssm_gate``'s."""
    norms = [m.group(1) for m in re.finditer(
        r' rsqrt\(.*op_name="([^"]*)"', texts[program])]
    assert norms
    assert not [n for n in norms
                if "hvd_mlp" in n or "hvd_moe" in n or "hvd_attention" in n]
    assert any("hvd_ssm_gate" in n for n in norms)
    assert any("hvd_mlp" in n for n in
               re.findall(r'op_name="([^"]*)"', texts[program]))
