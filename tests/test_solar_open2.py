"""The delta-rule hybrid model (``models/solar_open2.py``) through
``ServeEngine`` and ``ServeScheduler`` at a small size, against the plain
reference (``benchmarks/reference/solar_open2.py``, which imports nothing of
the program and runs the delta rule token by token): the KDA layers' slot
state beside the paged pool, the chunked rule, the gated attention layer, the
share of the routed experts, the third gate rule of ``parallel/moe.py``, what
the engine refuses for a model with slot state, and the scopes in the
compiled programs. The drive (a chunk, a decode step, two requests
interleaved) is ``test_granite_hybrid``'s: the engine knows no model."""

import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_granite_hybrid as drive
from horovod_tpu import metrics as M
from horovod_tpu.models import solar_open2 as so
from horovod_tpu.parallel import moe
from horovod_tpu.serving import Request, ServeScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import lowprec                          # noqa: E402
from benchmarks.reference import solar_open2 as ref         # noqa: E402

SMALL = dict(vocab_size=128, d_model=64, n_layers_total=4, gqa_layers=(1,),
             n_heads=4, n_kv_heads=2, head_dim=16, kda_n_heads=4,
             kda_head_dim=16, kda_chunk=32,
             n_routed_experts=8, top_k=3, routed_scaling=1.5, d_expert=32,
             d_shared=48, max_seq=128)

_engine, _chunk, _decode = drive._engine, drive._chunk, drive._decode


def _cfg(**kw):
    return so.SolarOpen2Config(**{**SMALL, "dtype": jnp.float32, **kw})


def _dims(cfg, **kw):
    return ref.Dims(**{**dict(
        layer_types=cfg.layer_types, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head=cfg.head_dim,
        kda_heads=cfg.kda_n_heads, kda_head=cfg.kda_head_dim,
        conv=cfg.kda_conv, n_routed=cfg.n_routed_experts, top_k=cfg.top_k,
        scaling=cfg.routed_scaling, first=cfg.expert_first,
        count=cfg.held_experts, eps=cfg.norm_eps), **kw})


def _params(cfg, seed=1):
    """Seeded weights with every norm scale and the router's selection bias
    off their neutral values, so none of them can be dropped unseen, and the
    decay's rates spread from weak to strong."""
    params = so.init_params(cfg, jax.random.PRNGKey(seed))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(flat))
    out = []
    for (path, a), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            a = a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        elif name.endswith("['router_bias']"):
            a = 0.05 * jax.random.normal(k, a.shape, a.dtype)
        elif name.endswith("['dt_bias']"):
            a = a + 3.0 * jax.random.uniform(k, a.shape, a.dtype)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _reference(dims, length):
    """One compiled pass a length: the sequences are padded to a multiple of
    32 (causal, so the padding changes nothing before it)."""
    return jax.jit(lambda params, tokens: ref.logits(
        lowprec.F32, dims, params, tokens, jnp.arange(length)))


def _reference_logits(cfg, params, tokens, **dims):
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n = len(tokens)
    padded = np.zeros((-(-n // 32) * 32,), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(_dims(cfg, **dims), len(padded))(
            wide, jnp.asarray(padded)))[:n]


def _check(cfg, params, served, atol, rtol=0.0, **dims):
    for seq, got in served:
        want = _reference_logits(cfg, params, np.array(seq, np.int32),
                                 **dims)
        assert len(got) >= 5
        for row, lg in got.items():
            np.testing.assert_allclose(lg, want[row], atol=atol, rtol=rtol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_engine_prefill_and_decode_through_pages_and_slot_state_match_the_reference(dtype):
    """Chunked prefill (a full bucket of 64 = two chunks of the rule, then a
    padded one of another size) carrying the delta-rule state and the three
    tails from chunk to chunk while another slot decodes, then decode:
    logits against the reference's one full pass with its token-by-token
    rule. The share held is experts 2..5 of 8."""
    cfg = _cfg(expert_first=2, expert_count=4, dtype=dtype)
    params = so.init_params(cfg, jax.random.PRNGKey(3))
    params = jax.tree.map(lambda a, b: b.astype(a.dtype), params,
                          _params(_cfg(expert_first=2, expert_count=4), 3))
    if dtype == jnp.bfloat16:
        # at this size a rounded score flips one of a token's three experts
        # of eight in a third of the rows, a whole expert's term each: the
        # bfloat16 pass fixes the choice by the selection bias (the float32
        # pass routes by the scores) and reads the products' rounding alone
        bias = params["layers"]["moe"]["router_bias"]
        params["layers"]["moe"]["router_bias"] = jnp.broadcast_to(
            2.0 * jnp.arange(bias.shape[-1], dtype=bias.dtype), bias.shape)
    eng, served = drive._interleaved(cfg, params)
    if dtype == jnp.float32:
        _check(cfg, params, served, atol=2e-4, rtol=2e-4)
    else:       # bfloat16 products: a few hundredths of the logits' spread
        spread = float(np.std(_reference_logits(
            cfg, params, np.array(served[0][0], np.int32))))
        _check(cfg, params, served, atol=0.15 * spread)
    # two kinds of cache side by side: pages for the one attention layer,
    # a state and three tails a slot for the three KDA layers
    assert [p.shape for p in eng.pools] == [
        (1, eng.pool.n_pages + 1, 8, cfg.n_kv_heads * cfg.head_dim)] * 2
    conv, state = eng.state[-2:]
    assert conv.shape == (3, cfg.kda_conv - 1, eng.slots, 3 * cfg.kda_width)
    assert state.shape == (3, eng.slots, cfg.kda_n_heads, cfg.kda_head_dim,
                           cfg.kda_head_dim)
    assert conv.dtype == state.dtype == jnp.float32
    s = eng.stats()["ssm"]
    assert s == {"state_bytes": conv.nbytes + state.nbytes, "slots": 3,
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
        sound = so.kda_chunk_scan
        monkeypatch.setattr(
            so, "kda_chunk_scan", lambda q, k, v, a, b, s, chunk:
            sound(q, k, v, a, b, jnp.zeros_like(s), chunk))
    elif fault == "conv_tail_from_padded_rows":
        monkeypatch.setattr(so.stack, "conv_tail",
                            lambda window, n_real, k1: window[-k1:])
    elif fault == "decode_advances_a_slot_mid_prefill":
        sound = so.kda_decode
        monkeypatch.setattr(
            so, "kda_decode", lambda cfg, mp, u, conv, state, layer, live:
            sound(cfg, mp, u, conv, state, layer, jnp.ones_like(live)))
    elif fault == "beta_without_the_factor_two":
        monkeypatch.setattr(so, "beta_of", jax.nn.sigmoid)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["scan_skips_the_carried_state",
                                   "conv_tail_from_padded_rows",
                                   "decode_advances_a_slot_mid_prefill",
                                   "beta_without_the_factor_two"])
def test_a_fault_in_the_delta_rule_fails_the_comparison(monkeypatch, fault):
    """Each of the ways to lose the state, and the rule's missing factor,
    that the comparison has to see: the same drive as the sound test, the
    program with the fault."""
    cfg = _cfg()
    params = _params(cfg, 3)
    _faulty(monkeypatch, fault)
    _, served = drive._interleaved(cfg, params)
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        _check(cfg, params, served, atol=2e-4, rtol=2e-4)


def test_the_reference_with_beta_in_zero_one_is_another_model():
    """The switch the configuration sets (``kda_allow_neg_eigval``) is the
    reference's too: without it the program's logits are not its."""
    cfg = _cfg()
    params = _params(cfg, 3)
    _, served = drive._interleaved(cfg, params)
    _check(cfg, params, served, atol=2e-4, rtol=2e-4)
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        _check(cfg, params, served, atol=2e-4, rtol=2e-4, beta_scale=1.0)


# ---------------------------------------------------------------------------
# the chunked rule against the recurrence
# ---------------------------------------------------------------------------

H, D = 4, 16
WEAK, STRONG = 0.02, 5.0        # the log-decay's scale a step


def _rule_inputs(rows, strength, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (rows, H, D))
    k = jax.random.normal(ks[1], (rows, H, D))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, H, D))
    a = -strength * jax.nn.softplus(jax.random.normal(ks[3], (rows, H, D)))
    b = so.beta_of(jax.random.normal(ks[4], (rows, H)))
    return q, k, v, a, b


def _token_by_token(q, k, v, a, b, s):
    """``kda_step`` a row at a time (the decode path's recurrence)."""
    out = []
    for t in range(q.shape[0]):
        o, s = so.kda_step(q[t:t + 1], k[t:t + 1], v[t:t + 1], a[t:t + 1],
                           b[t:t + 1], s[None])
        out.append(o[0])
        s = s[0]
    return jnp.stack(out), s


@pytest.mark.parametrize("strength", [WEAK, STRONG], ids=["weak", "strong"])
@pytest.mark.parametrize("carried", [False, True], ids=["zeros", "carried"])
@pytest.mark.parametrize("rows, real", [(64, 64), (256, 256), (128, 77),
                                        (32, 2)],
                         ids=["one_chunk", "four_chunks", "padded",
                              "two_real_rows"])
def test_the_chunked_rule_is_the_recurrence_token_by_token(strength, carried,
                                                           rows, real):
    """Prefill's chunked form (chunks of 64 in sub-blocks of 16) against
    decode's one-step form over the real rows, and against the reference's
    own rule; a padded row (a = 0, b = 0) moves neither the outputs before
    it nor the state."""
    q, k, v, a, b = _rule_inputs(rows, strength)
    live = jnp.arange(rows) < real
    a, b = a * live[:, None, None], b * live[:, None]
    s0 = (jax.random.normal(jax.random.PRNGKey(9), (H, D, D)) if carried
          else jnp.zeros((H, D, D)))
    o, s = so.kda_chunk_scan(q, k, v, a, b, s0, 64)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    want_o, want_s = _token_by_token(q[:real], k[:real], v[:real], a[:real],
                                     b[:real], s0)
    np.testing.assert_allclose(o[:real], want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-5, rtol=1e-5)
    if not carried:
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                o[:real], ref.delta_rule(lowprec.F32, q[:real], k[:real],
                                         v[:real], a[:real], b[:real]),
                atol=1e-5, rtol=1e-5)


def test_the_strong_decay_is_one_at_which_the_unsplit_form_overflows():
    """``e^{-G}`` over a chunk's 64 rows, which the chunked rule never
    forms, is not finite in float32 at the strong decay the test above runs
    (and is at the weak one)."""
    for strength, finite in ((WEAK, True), (STRONG, False)):
        *_, a, _ = _rule_inputs(64, strength)
        grown = jnp.exp(-jnp.cumsum(a, axis=0))
        assert bool(jnp.isfinite(grown).all()) is finite, strength


def test_beta_reaches_into_one_two():
    raw = jnp.linspace(-6.0, 6.0, 25)
    b = np.asarray(so.beta_of(raw))
    assert b.min() > 0 and b.max() < 2 and (b > 1).sum() == 12
    # through the layer's own inputs too
    cfg = _cfg()
    mp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"][so.KDA])
    u = jax.random.normal(jax.random.PRNGKey(0), (40, cfg.d_model))
    qkv, f, beta, _ = so.kda_project(cfg, mp, 3.0 * u)
    *_, a, b = so.kda_inputs(cfg, mp, qkv, f, beta, jnp.ones((40,), bool))
    assert float(b.max()) > 1.5 and float(b.min()) < 0.5
    assert float(a.max()) <= 0.0


def test_bucket_padding_moves_neither_the_state_nor_the_tails():
    """One layer's prefill on 21 real rows of a bucket of 32, twice with
    different rows behind them: the stored state and tails are bit-equal,
    the tails are the convolutions' INPUTS of rows 18, 19, 20 (three streams
    wide), the real rows' outputs agree."""
    cfg = _cfg()
    mp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"][so.KDA])
    conv0, state0 = (jnp.ones(s.shape, s.dtype)
                     for s in so.slot_state(cfg, 2))
    u = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    other = u.at[21:].set(7.0 * jax.random.normal(
        jax.random.PRNGKey(2), (11, cfg.d_model)))
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    runs = [so.kda_prefill(cfg, mp, rows, conv0, state0, i32(0), i32(1),
                           i32(0), i32(21)) for rows in (u, other)]
    (out, conv, state), (out2, conv2, state2) = runs
    np.testing.assert_array_equal(conv, conv2)
    np.testing.assert_array_equal(state, state2)
    np.testing.assert_allclose(out[:21], out2[:21], atol=1e-6)
    qkv = so.kda_project(cfg, mp, u)[0]
    np.testing.assert_array_equal(conv[0, :, 1], qkv[18:21])
    assert conv.shape[-1] == 3 * cfg.kda_width
    # the other slot and the other layers are as they were; this one moved
    np.testing.assert_array_equal(conv[:, :, 0], conv0[:, :, 0])
    np.testing.assert_array_equal(state[1:], state0[1:])
    np.testing.assert_array_equal(state[0, 0], state0[0, 0])
    assert not np.array_equal(state[0, 1], state0[0, 1])


# ---------------------------------------------------------------------------
# the share of the routed experts, and the third gate rule
# ---------------------------------------------------------------------------

def _moe_inputs(cfg, rows=10, seed=3):
    ep = jax.tree.map(lambda a: a[0], _params(cfg, seed)["layers"]["moe"])
    return ep, jax.random.normal(jax.random.PRNGKey(seed), (rows, cfg.d_model))


def _share(ep, first, count):
    sl = slice(first, first + count)
    return {**ep, **{k: ep[k][sl] for k in ("w_gate", "w_up", "w_down")}}


def _program_moe(cfg, ep, h, first, count, shared=True):
    """``experts`` on the share, less the residual it adds to."""
    from horovod_tpu.models import granite_hybrid as stack
    share = _share(ep, first, count)
    if not shared:
        share["shared"] = jax.tree.map(jnp.zeros_like, ep["shared"])
    cut = dataclasses.replace(cfg, expert_first=first, expert_count=count)
    with jax.default_matmul_precision("highest"):
        out, _ = stack.experts(cut, share, h, None)
    return np.asarray(out - h)


def _reference_moe(cfg, ep, h, first, count, shared=True):
    dims = _dims(cfg, first=first, count=count)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.moe(
            lowprec.F32, dims, ref.rmsnorm(h, ep["norm"], cfg.norm_eps),
            _share(ep, first, count), shared=shared))


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


def test_sigmoid_gates_sum_to_the_scaling_and_the_bias_moves_only_the_choice():
    cfg = _cfg()
    ep, u = _moe_inputs(cfg, rows=12)
    r = cfg.route(u, ep)
    assert r.experts.shape == r.gates.shape == (12, cfg.top_k)
    assert r.experts.dtype == jnp.int32 and r.gates.dtype == jnp.float32
    np.testing.assert_allclose(np.sum(r.gates, axis=-1), cfg.routed_scaling,
                               atol=1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray(
        jnp.dot(u, ep["router"], precision="highest"))))
    bias = np.asarray(ep["router_bias"])
    for t in range(12):
        top = np.argsort(-(s[t] + bias))[:cfg.top_k]
        assert sorted(r.experts[t].tolist()) == sorted(top.tolist())
        chosen = s[t, np.asarray(r.experts[t])]
        np.testing.assert_allclose(
            r.gates[t], cfg.routed_scaling * chosen / chosen.sum(), atol=1e-6)
    # a bias that lifts expert 7 above all puts it among every row's chosen
    # and leaves its weight the score's own
    lifted = moe.topk_sigmoid_route(
        u, ep["router"], jnp.zeros((8,)).at[7].set(10.0), cfg.top_k, 1.0)
    assert np.all(np.any(np.asarray(lifted.experts) == 7, axis=-1))
    at = np.argmax(np.asarray(lifted.experts) == 7, axis=-1)
    g7 = np.take_along_axis(np.asarray(lifted.gates), at[:, None], -1)[:, 0]
    assert np.all(g7 < 1.0) and np.all(g7 > 0.0)


def test_the_two_older_gate_rules_give_what_they_gave():
    """One product and one top-k under three rules: the first two against
    their own definitions, written out."""
    cfg = _cfg()
    ep, u = _moe_inputs(cfg, rows=12)
    logits = np.asarray(jnp.dot(u, ep["router"], precision="highest"))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    bias = 0.05 * np.arange(8, dtype=np.float32)
    old = moe.topk_route(u, ep["router"], jnp.asarray(bias), 3, 2.0)
    soft = moe.topk_softmax_route(u, ep["router"], 3)
    for t in range(12):
        top = np.argsort(-(p[t] + bias), kind="stable")[:3]
        assert old.experts[t].tolist() == top.tolist()
        np.testing.assert_allclose(old.gates[t], 2.0 * p[t, top], atol=1e-6)
        top = np.argsort(-logits[t], kind="stable")[:3]
        assert soft.experts[t].tolist() == top.tolist()
        e = np.exp(logits[t, top] - logits[t, top].max())
        np.testing.assert_allclose(soft.gates[t], e / e.sum(), atol=1e-6)
    assert old.experts.dtype == soft.experts.dtype == jnp.int32


# ---------------------------------------------------------------------------
# slots and their state in the engine
# ---------------------------------------------------------------------------

def test_a_slot_reused_by_a_second_request_gives_a_fresh_engines_logits():
    """Nobody clears a released slot's state or tails: a prompt's first
    chunk starts from zeros whatever the slot holds."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(4)
    first = rng.integers(0, cfg.vocab_size, 50).astype(np.int32)
    second = rng.integers(0, cfg.vocab_size, 37).astype(np.int32)
    eng = _engine(cfg, params, slots=1)
    slot, _ = drive._serve_one(eng, first, 3)
    assert float(jnp.abs(eng.state[-1][:, slot]).max()) > 0     # left behind
    again, reused = drive._serve_one(eng, second, 3)
    assert again == slot
    _, fresh = drive._serve_one(_engine(cfg, params, slots=1), second, 3)
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
    conv, state = (np.asarray(s) for s in eng.state[-2:])
    np.testing.assert_array_equal(conv[:, :, a], before[0][:, :, a])
    np.testing.assert_array_equal(state[:, a], before[1][:, a])
    assert np.abs(state[:, a]).max() > 0
    # the decoding slot's state and tails moved, the empty slot's did not
    assert not np.array_equal(state[:, b], before[1][:, b])
    assert not np.array_equal(conv[:, :, b], before[0][:, :, b])
    free = ({0, 1, 2} - {a, b}).pop()
    np.testing.assert_array_equal(state[:, free], before[1][:, free])
    np.testing.assert_array_equal(conv[:, :, free], before[0][:, :, free])


def test_scheduler_run_with_decode_ahead_gives_the_direct_loops_tokens():
    """``ServeScheduler`` unchanged, no branch for this model: more requests
    than slots through admission, chunked prefill interleaved with batched
    decode and slot turnover, each step queued before the last is read,
    against the reference's greedy continuation and against the direct
    loop (``engine.prefill`` then ``decode_step(tokens)``)."""
    cfg = _cfg(expert_first=4, expert_count=4)
    params = _params(cfg, seed=2)
    sizes, n_out = (5, 70, 19, 40, 9, 66), 5
    rng = np.random.default_rng(5)
    reqs = [Request(rid=rid, prompt=rng.integers(
        0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=n_out)
        for rid, n in enumerate(sizes)]
    eng = _engine(cfg, params)
    done = ServeScheduler(eng).run(reqs)
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
    for kw, reason in (({"gqa_layers": ()}, "at least one layer of each"),
                       ({"gqa_layers": (0, 1, 2, 3)}, "at least one layer"),
                       ({"n_kv_heads": 3}, "whole groups"),
                       ({"expert_first": 6, "expert_count": 4},
                        "does not lie")):
        cfg = _cfg(**kw)
        with pytest.raises(ValueError, match=reason):
            cfg.serve_model().check(cfg, "off")


def test_the_published_stack_is_one_attention_layer_in_four():
    cfg = so.SolarOpen2Config()
    assert cfg.n_layers == 48 and cfg.count("attention") == 12
    assert cfg.count(so.KDA) == 36
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == list(range(0, 48, 4))
    assert cfg.runs()[:3] == [("attention", 0, 0, 1), (so.KDA, 1, 0, 3),
                              ("attention", 4, 1, 1)]
    cut = dataclasses.replace(cfg, n_layers_total=4, gqa_layers=(0,),
                              expert_count=40, vocab_size=24576)
    shapes = so.param_shapes(cut)
    assert shapes["layers"][so.KDA]["w_qkv"][0] == (3, 4096, 3 * 8192)
    assert shapes["layers"]["attention"]["wz"][0] == (1, 4096, 8192)
    assert shapes["layers"]["moe"]["router"][0] == (4, 4096, 320)
    assert shapes["layers"]["moe"]["w_gate"][0] == (4, 40, 4096, 1280)
    state = so.slot_state(cut, 128)
    assert [s.shape for s in state] == [(3, 3, 128, 24576),
                                        (3, 128, 64, 128, 128)]
    assert all(s.dtype == jnp.float32 for s in state)


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

    for scope in ("hvd_kda_proj", "hvd_kda_conv", "hvd_kda_scan",
                  "hvd_kda_gate"):
        assert some("hvd_kda/" + scope), scope
    for scope in ("hvd_moe_router", "hvd_moe_experts", "hvd_moe_combine"):
        assert some("hvd_moe/" + scope), scope
    for scope in ("hvd_attention", "hvd_kv_write", "hvd_mlp"):
        assert some(scope), scope
    assert texts[program].splitlines()[0].startswith(
        "HloModule jit_hvd_serve_" + program.split("_")[1])
    # the state's and the tails' read and write stand under their scopes,
    # the attention layer's gate under the attention's
    assert some("hvd_kda_scan", "dynamic_update_slice")
    assert some("hvd_kda_conv", "dynamic_update_slice")
    # (the gate's own product, and its sigmoid, which the CPU compiler
    # spells out from its negation on)
    assert any(n.endswith("hvd_attention/dot_general") for n in names)
    assert any(n.endswith(("hvd_attention/logistic", "hvd_attention/neg"))
               for n in names)
    assert not some("hvd_ssm")


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_32"])
def test_no_norm_stands_under_hvd_mlp(texts, program):
    """The shared expert stands under ``hvd_mlp``, the norm before the
    expert block outside it (and outside ``hvd_moe``), as the dense block's
    does; the head norm of a KDA layer is ``hvd_kda_gate``'s, q's and k's
    unit length ``hvd_kda_scan``'s."""
    norms = [m.group(1) for m in re.finditer(
        r' rsqrt\(.*op_name="([^"]*)"', texts[program])]
    assert norms
    assert not [n for n in norms
                if "hvd_mlp" in n or "hvd_moe" in n or "hvd_attention" in n]
    assert any("hvd_kda_gate" in n for n in norms)
    assert any("hvd_kda_scan" in n for n in norms)
    assert any("hvd_mlp" in n for n in
               re.findall(r'op_name="([^"]*)"', texts[program]))
