"""The gated DeltaNet hybrid model (``models/olmo_hybrid.py``) through
``ServeEngine`` and ``ServeScheduler`` at a small size with rectangular
state heads (keys of 32, values of 64), against the plain reference
(``benchmarks/reference/olmo_hybrid.py``, which imports nothing of the
program and runs the delta rule token by token): the slot state in head
pairs beside the paged pool, the shared delta rule (``models/delta_rule.py``)
with one decay a head, the post-norm layers, the q/k norms, the dense SwiGLU
on the shared hybrid step, what the engine refuses for a model with slot
state, and the scopes in the compiled programs. The drive (a chunk, a decode
step, two requests interleaved) is ``test_granite_hybrid``'s."""

import dataclasses
import functools
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_granite_hybrid as drive
from horovod_tpu import metrics as M
from horovod_tpu.models import delta_rule, granite_hybrid as stack
from horovod_tpu.models import olmo_hybrid as oh
from horovod_tpu.serving import Request, ServeScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import lowprec                          # noqa: E402
from benchmarks.reference import olmo_hybrid as ref         # noqa: E402

SMALL = dict(vocab_size=128, d_model=64,
             layer_types=("gdn", "gdn", "attention", "gdn"),
             n_heads=4, n_kv_heads=4, head_dim=16, d_ff=96, gdn_n_heads=4,
             gdn_d_key=32, gdn_d_value=64, gdn_chunk=16, max_seq=128)

_engine, _chunk, _decode = drive._engine, drive._chunk, drive._decode


def _cfg(**kw):
    return oh.OlmoHybridConfig(**{**SMALL, "dtype": jnp.float32, **kw})


def _dims(cfg, **kw):
    return ref.Dims(**{**dict(
        layer_types=cfg.layer_types, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, head=cfg.head_dim,
        gdn_heads=cfg.gdn_n_heads, d_key=cfg.gdn_d_key,
        d_value=cfg.gdn_d_value, conv=cfg.gdn_conv, eps=cfg.norm_eps), **kw})


def _params(cfg, seed=1):
    """Seeded weights with every norm scale off 1, so none of them can be
    dropped unseen, the embedding at a deviation of 1 and the decay's steps
    spread from weak to strong."""
    params = oh.init_params(cfg, jax.random.PRNGKey(seed))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(flat))
    out = []
    for (path, a), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            a = a + 0.2 * jax.random.normal(k, a.shape, a.dtype)
        elif name == "['embed']":
            a = a * cfg.d_model ** 0.5
        elif name.endswith("['dt_bias']"):
            a = a + 3.0 * jax.random.uniform(k, a.shape, a.dtype)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _reference(dims, length):
    """One compiled pass a length (the sequences padded to a multiple of 32:
    causal, so the padding changes nothing before it)."""
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


# float32 throughout: the engine's chunked rule against the reference's
# token-by-token rule, ~30 rows deep and 4 layers, agree to ~1e-5 of logits
# of a deviation of ~1; 2e-4 leaves room for the order of the sums and is
# ten times under what a state rounded to bfloat16 moves (the fault below)
ATOL = 2e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_engine_prefill_and_decode_through_pages_and_slot_state_match_the_reference(dtype):
    """Chunked prefill (a full bucket of 64 = four chunks of the rule, then
    a padded one of another size) carrying the delta-rule state and the
    three tails from chunk to chunk while another slot decodes, then decode:
    logits against the reference's one full pass."""
    cfg = _cfg(dtype=dtype)
    params = oh.init_params(cfg, jax.random.PRNGKey(3))
    params = jax.tree.map(lambda a, b: b.astype(a.dtype), params,
                          _params(_cfg(), 3))
    eng, served = drive._interleaved(cfg, params)
    if dtype == jnp.float32:
        _check(cfg, params, served, atol=ATOL, rtol=ATOL)
    else:       # bfloat16 products: a few hundredths of the logits' spread
        spread = float(np.std(_reference_logits(
            cfg, params, np.array(served[0][0], np.int32))))
        _check(cfg, params, served, atol=0.15 * spread)
    # two kinds of cache side by side: pages for the one attention layer, a
    # state (heads in pairs: 64 values twice on 128 lanes) and three tails a
    # slot for the three GDN layers
    assert [p.shape for p in eng.pools] == [
        (1, eng.pool.n_pages + 1, 8, cfg.n_kv_heads * cfg.head_dim)] * 2
    conv, state = eng.state[-2:]
    assert conv.shape == (3, cfg.gdn_conv - 1, eng.slots, cfg.conv_dim)
    assert cfg.conv_dim == 2 * 4 * 32 + 4 * 64
    assert state.shape == (3, eng.slots, 2, 32, 128)
    assert conv.dtype == state.dtype == jnp.float32
    s = eng.stats()
    assert "moe" not in s               # a dense stack keeps no routing
    assert s["ssm"] == {"state_bytes": conv.nbytes + state.nbytes,
                        "resident_bytes": conv.nbytes + state.nbytes,
                        "slots": 3, "layers": 3, "resets": 2,
                        "chunks_carried": 1, "decode_rows": 1 + 2 * 4}
    assert M.get_registry().get("hvd_serve_ssm_resident_bytes").value \
        == s["ssm"]["resident_bytes"]
    assert len(eng.state) == 3          # the counters, the tails, the state


def _faulty(monkeypatch, fault):
    if fault == "scan_skips_the_carried_state":
        sound = oh.gdn_chunk_scan
        monkeypatch.setattr(
            oh, "gdn_chunk_scan", lambda q, k, v, a, b, s, chunk:
            sound(q, k, v, a, b, jnp.zeros_like(s), chunk))
    elif fault == "conv_tail_from_padded_rows":
        monkeypatch.setattr(stack, "conv_tail",
                            lambda window, n_real, k1: window[-k1:])
    elif fault == "decode_advances_a_slot_mid_prefill":
        sound = oh.gdn_decode
        monkeypatch.setattr(
            oh, "gdn_decode", lambda cfg, mp, u, conv, state, layer, live:
            sound(cfg, mp, u, conv, state, layer, jnp.ones_like(live)))
    elif fault == "beta_without_the_factor_two":
        monkeypatch.setattr(oh, "beta_of", jax.nn.sigmoid)
    elif fault == "state_kept_in_bfloat16":
        monkeypatch.setattr(oh, "STATE_DTYPE", jnp.bfloat16)
    elif fault == "norms_before_the_sublayers":
        monkeypatch.setattr(oh.OlmoHybridConfig, "post_norm", False)
    elif fault == "attention_without_its_qk_norms":
        sound = stack._projected
        monkeypatch.setattr(
            stack, "_projected", lambda cfg, ap, u, name: sound(
                cfg, {k: v for k, v in ap.items() if "_norm" not in k},
                u, name))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["scan_skips_the_carried_state",
                                   "conv_tail_from_padded_rows",
                                   "decode_advances_a_slot_mid_prefill",
                                   "beta_without_the_factor_two",
                                   "state_kept_in_bfloat16",
                                   "norms_before_the_sublayers",
                                   "attention_without_its_qk_norms"])
def test_a_fault_in_the_model_fails_the_comparison(monkeypatch, fault):
    """Each of the ways to lose the state, the rule's missing factor, a
    state in a narrower type and the other reading of the norms, that the
    comparison has to see: the same drive as the sound test, the program
    with the fault."""
    cfg = _cfg()
    params = _params(cfg, 3)
    _faulty(monkeypatch, fault)
    eng, served = drive._interleaved(cfg, params)
    if fault == "state_kept_in_bfloat16":
        assert eng.state[-1].dtype == jnp.bfloat16
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        _check(cfg, params, served, atol=ATOL, rtol=ATOL)


def test_the_reference_with_beta_in_zero_one_is_another_model():
    """``linear_allow_neg_eigval`` is the reference's switch too: without it
    the program's logits are not its."""
    cfg = _cfg()
    params = _params(cfg, 3)
    _, served = drive._interleaved(cfg, params)
    _check(cfg, params, served, atol=ATOL, rtol=ATOL)
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        _check(cfg, params, served, atol=ATOL, rtol=ATOL, beta_scale=1.0)


# ---------------------------------------------------------------------------
# the shared rule with one decay a head, against the recurrence
# ---------------------------------------------------------------------------

H, DK, DV = 4, 32, 64
WEAK, STRONG = 0.02, 5.0        # the log-decay's scale a step


def _rule_inputs(rows, strength, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (rows, H, DK))
    k = jax.random.normal(ks[1], (rows, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, H, DV))
    g = -strength * jax.nn.softplus(jax.random.normal(ks[3], (rows, H)))
    # beta near 2: the transition along k^ is close to a reflection
    b = delta_rule.beta_of(4.0 + jax.random.normal(ks[4], (rows, H)))
    return q, k, v, g[..., None], b


def _token_by_token(q, k, v, a, b, s, layout):
    """The one-step rule a row at a time (the decode path's), on the states
    in ``layout``; returns the outputs and the state one head by one."""
    out, s = [], layout.from_heads(s)[None]
    for t in range(q.shape[0]):
        o, s = delta_rule.step(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                               a[t:t + 1], b[t:t + 1], s, layout=layout)
        out.append(o[0])
    return jnp.stack(out), layout.to_heads(s[0])


@pytest.mark.parametrize("strength", [WEAK, STRONG], ids=["weak", "strong"])
@pytest.mark.parametrize("carried", [False, True], ids=["zeros", "carried"])
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("rows, real", [(64, 64), (128, 77)],
                         ids=["whole", "padded"])
def test_the_chunked_rule_is_the_one_step_rule_token_by_token(
        strength, carried, chunk, rows, real):
    """Prefill's chunked form with one decay a head against decode's
    one-step form on the states in head pairs, over the real rows; a padded
    row (g = 0, b = 0) moves neither the outputs before it nor the state;
    nothing overflows at the strong decay with beta near 2."""
    q, k, v, a, b = _rule_inputs(rows, strength)
    live = jnp.arange(rows) < real
    a, b = a * live[:, None, None], b * live[:, None]
    assert float(b[:real].min()) > 1.5
    assert float(jnp.median(b[:real])) > 1.9
    s0 = (jax.random.normal(jax.random.PRNGKey(9), (H, DK, DV)) if carried
          else jnp.zeros((H, DK, DV)))
    o, s = delta_rule.chunk_scan(q, k, v, a, b, s0, chunk,
                                 delta_rule.per_head_matrices)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    layout = delta_rule.grouped(H, DV)
    assert layout.group == 2
    want_o, want_s = _token_by_token(q[:real], k[:real], v[:real], a[:real],
                                     b[:real], s0, layout)
    np.testing.assert_allclose(o[:real], want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)
    if not carried:
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                o[:real], ref.delta_rule(lowprec.F32, q[:real], k[:real],
                                         v[:real], a[:real, :, 0], b[:real]),
                atol=2e-5, rtol=2e-5)


def test_one_decay_a_head_is_the_per_channel_rule_with_equal_channels():
    """The two builders of the triangular matrices are one rule: a decay
    a head broadcast over the key channels, through KDA's sub-blocks, gives
    what the one product a matrix gives; and the one-step rule reads the
    same in both layouts of the states."""
    q, k, v, a, b = _rule_inputs(64, STRONG, seed=4)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (H, DK, DV))
    by_head = delta_rule.chunk_scan(q, k, v, a, b, s0, 32,
                                    delta_rule.per_head_matrices)
    by_channel = delta_rule.chunk_scan(
        q, k, v, jnp.broadcast_to(a, k.shape), b, s0, 32,
        delta_rule.per_channel_matrices)
    for x, y in zip(by_head, by_channel):
        np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5)
    pairs = delta_rule.grouped(H, DV)
    o1, s1 = delta_rule.step(q[:3], k[:3], v[:3], a[:3], b[:3],
                             jnp.stack([s0] * 3))
    o2, s2 = delta_rule.step(q[:3], k[:3], v[:3], a[:3], b[:3],
                             jnp.stack([pairs.from_heads(s0)] * 3),
                             layout=pairs)
    np.testing.assert_allclose(o1, o2, atol=1e-6)
    np.testing.assert_allclose(s1[1], pairs.to_heads(s2[1]), atol=1e-6)


def test_the_state_lies_in_whole_tiles_and_in_float32():
    """At the cell's sizes: a slot's state ``[15, 96, 384]`` and tails
    ``[3, 11520]`` a layer, float32, and their bytes in the device's tiles of
    8 x 128 are their own; a head a row ``[30, 96, 192]`` would hold a third
    more."""
    cfg = oh.OlmoHybridConfig(layer_types=oh.PUBLISHED_LAYER_TYPES[:8])
    conv, state = oh.slot_state(cfg, 64)
    assert conv.shape == (6, 3, 64, 11520) and conv.dtype == jnp.float32
    assert state.shape == (6, 64, 15, 96, 384)
    assert state.dtype == jnp.float32
    logical = 6 * 64 * (30 * 96 * 192 + 3 * 11520) * 4
    assert conv.size * 4 + state.size * 4 == logical     # 0.902 GB

    def on_tpu(shape):
        return types.SimpleNamespace(
            shape=shape, dtype=np.dtype("float32"),
            format=types.SimpleNamespace(layout=types.SimpleNamespace(
                major_to_minor=tuple(range(len(shape))),
                tiling=((8, 128),))))
    assert stack.resident_bytes(on_tpu(conv.shape), on_tpu(state.shape)) \
        == logical
    one_by_one = (6, 64, 30, 96, 192)
    assert stack.resident_bytes(on_tpu(one_by_one)) \
        == 6 * 64 * 30 * 96 * 256 * 4
    # on a device that tiles nothing, an array's own bytes
    x = jnp.zeros((3, 5, 7), jnp.float32)
    assert stack.resident_bytes(x) == x.nbytes


def test_bucket_padding_moves_neither_the_state_nor_the_tails():
    """One layer's prefill on 21 real rows of a bucket of 32, twice with
    different rows behind them: the stored state and tails are bit-equal,
    the tails are the convolutions' INPUTS of rows 18, 19, 20, the real
    rows' outputs agree; the other slot is untouched."""
    cfg = _cfg()
    mp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"][oh.GDN])
    conv0, state0 = (jnp.ones(s.shape, s.dtype)
                     for s in oh.slot_state(cfg, 2))
    u = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    other = u.at[21:].set(7.0 * jax.random.normal(
        jax.random.PRNGKey(2), (11, cfg.d_model)))
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    runs = [oh.gdn_prefill(cfg, mp, rows, conv0, state0, i32(0), i32(1),
                           i32(0), i32(21)) for rows in (u, other)]
    (out, conv, state), (out2, conv2, state2) = runs
    np.testing.assert_array_equal(conv, conv2)
    np.testing.assert_array_equal(state, state2)
    np.testing.assert_allclose(out[:21], out2[:21], atol=1e-6)
    qkv = oh.gdn_project(cfg, mp, u)[0]
    np.testing.assert_array_equal(conv[0, :, 1], qkv[18:21])
    np.testing.assert_array_equal(conv[:, :, 0], conv0[:, :, 0])
    np.testing.assert_array_equal(state[1:], state0[1:])
    np.testing.assert_array_equal(state[0, 0], state0[0, 0])
    assert not np.array_equal(state[0, 1], state0[0, 1])


def test_a_decode_step_between_two_chunks_leaves_the_prefilling_slots_state_bit_equal():
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg))
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
    assert not np.array_equal(state[:, b], before[1][:, b])
    free = ({0, 1, 2} - {a, b}).pop()
    np.testing.assert_array_equal(state[:, free], before[1][:, free])
    np.testing.assert_array_equal(conv[:, :, free], before[0][:, :, free])


def test_scheduler_run_with_decode_ahead_gives_the_references_greedy_tokens():
    """``ServeScheduler`` unchanged, no branch for this model: more requests
    than slots through admission, chunked prefill interleaved with batched
    decode and slot turnover, each step queued before the last is read,
    against the reference's greedy continuation."""
    cfg = _cfg()
    params = _params(cfg, seed=2)
    sizes, n_out = (5, 70, 19, 40, 9), 4
    rng = np.random.default_rng(5)
    reqs = [Request(rid=rid, prompt=rng.integers(
        0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=n_out)
        for rid, n in enumerate(sizes)]
    eng = _engine(cfg, params)
    done = ServeScheduler(eng).run(reqs)
    assert len(done) == len(sizes)
    for req in done:
        assert req.error is None and len(req.tokens) == n_out
        seq = list(req.prompt) + list(req.tokens)
        want = _reference_logits(cfg, params, np.array(seq[:-1], np.int32))
        n = len(req.prompt)
        assert list(req.tokens) == [
            int(np.argmax(want[n - 1 + i])) for i in range(n_out)]
    s = eng.stats()["ssm"]
    assert s["resets"] == len(sizes) and s["chunks_carried"] == 1


@pytest.mark.parametrize("kw, reason", [
    ({"prefix_cache": True}, "skip prompt tokens the recurrent layers"),
    ({"draft": "ngram:2", "spec_k": 2}, "plain decode only"),
    ({"draft": "truncate:1", "spec_k": 2}, "plain decode only"),
], ids=["prefix_cache", "ngram", "truncate"])
def test_what_slot_state_cannot_give_is_refused_with_the_reason(kw, reason):
    cfg = _cfg()
    with pytest.raises(ValueError, match=reason):
        _engine(cfg, _params(cfg), **kw)


def test_a_config_the_bodies_do_not_serve_is_refused():
    for kw, reason in (({"layer_types": ("gdn",) * 4}, "at least one"),
                       ({"layer_types": ("attention",) * 4}, "at least one"),
                       ({"layer_types": ("gdn", "mamba")}, "must mix"),
                       ({"n_kv_heads": 3}, "whole groups"),
                       ({"tp_axis": "model"}, "one chip's share")):
        cfg = _cfg(**kw)
        with pytest.raises(ValueError, match=reason):
            cfg.serve_model().check(cfg, "off")


def test_the_published_stack_and_the_cut():
    """Olmo-Hybrid-7B's sizes: (3 GDN, 1 attention) x 8, 7.43 B parameters;
    one chip's share, layers 0-7 with the embedding and the head, 2.436 B."""
    cfg = oh.OlmoHybridConfig()
    assert cfg.n_layers == 32 and cfg.count("attention") == 8
    assert cfg.runs()[:2] == [(oh.GDN, 0, 0, 3), ("attention", 3, 0, 1)]
    assert not cfg.has_experts and cfg.post_norm
    assert cfg.layout == delta_rule.Heads(2, 192)

    def count(c):
        return sum(int(np.prod(s)) for s, _ in jax.tree.leaves(
            oh.param_shapes(c), is_leaf=stack._is_shape))
    assert count(cfg) == pytest.approx(7.431e9, rel=1e-3)
    cut = dataclasses.replace(cfg, layer_types=cfg.layer_types[:8])
    assert count(cut) == pytest.approx(2.436e9, rel=1e-3)
    shapes = oh.param_shapes(cut)
    assert shapes["layers"][oh.GDN]["w_qkv"][0] == (6, 3840, 11520)
    assert shapes["layers"]["attention"]["q_norm"][0] == (2, 3840)
    assert shapes["layers"]["mlp"]["w_down"][0] == (8, 11008, 3840)


# ---------------------------------------------------------------------------
# scopes in the compiled programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def texts():
    cfg = _cfg()
    eng = _engine(cfg, _params(cfg))
    return {label: eng.executable_text(label)
            for label in ("serve_decode", "serve_prefill_32")}


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_32"])
def test_the_scopes_are_in_the_compiled_programs(texts, program):
    names = set(re.findall(r'op_name="([^"]*)"', texts[program]))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("hvd_gdn_proj", "hvd_gdn_conv", "hvd_gdn_scan",
                  "hvd_gdn_gate"):
        assert some("hvd_gdn/" + scope), scope
    for scope in ("hvd_attention", "hvd_kv_write", "hvd_mlp"):
        assert some(scope), scope
    assert some("hvd_gdn_scan", "dynamic_update_slice")
    assert some("hvd_gdn_conv", "dynamic_update_slice")
    assert not some("hvd_moe") and not some("hvd_ssm") \
        and not some("hvd_kda")


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill_32"])
def test_no_norm_stands_under_hvd_mlp(texts, program):
    """The SwiGLU stands under ``hvd_mlp``, the norm after it outside; the
    head norm of a GDN layer is ``hvd_gdn_gate``'s, q's and k's unit length
    ``hvd_gdn_scan``'s."""
    norms = [m.group(1) for m in re.finditer(
        r' rsqrt\(.*op_name="([^"]*)"', texts[program])]
    assert norms
    assert not [n for n in norms if "hvd_mlp" in n or "hvd_attention" in n]
    assert any("hvd_gdn_gate" in n for n in norms)
    assert any("hvd_gdn_scan" in n for n in norms)


@pytest.mark.parametrize("row, pieces", [(3840, 2), (1024, 1)])
def test_a_large_page_is_taken_in_pieces_and_gives_the_same_rows(row, pieces):
    """A page of 128 rows of 3840 bfloat16 numbers (960 KiB, this model's)
    is over the 512 KiB the TPU compiler gathers in place, so
    ``take_pages`` takes it as two pieces of 64 rows; a page of 1024 lanes
    (Granite's, Solar's) is taken whole, the same instruction as before.
    Either way the rows are ``jnp.take``'s."""
    from horovod_tpu.serving import kv_cache as kvc
    pages = jax.random.normal(jax.random.PRNGKey(0), (5, 128, row),
                              jnp.float32).astype(jnp.bfloat16)
    table = jnp.asarray([[3, 1, 4], [0, 2, 2]], jnp.int32)
    got = kvc.take_pages(pages, table)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(
        jnp.take(pages, table, axis=0), np.float32))
    jaxpr = str(jax.make_jaxpr(kvc.take_pages)(pages, table))
    assert (f"10,64,{row}" in jaxpr) == (pieces > 1)
