"""The DeepSeek-V3 stack of Kimi K2 (``models/kimi_k2.py``: latent attention
under a YaRN-stretched rotary, a leading dense layer, sigmoid-routed experts
plus a shared expert) through ``ServeEngine`` and ``ServeScheduler`` at a
small size, against the plain reference (``benchmarks/reference/kimi_k2.py``,
which imports nothing of the program); the shares of its expert layer; the
rotary's YaRN frequencies; and that the package's one rotary and LongCat's
programs came through unchanged."""

import collections
import dataclasses
import hashlib
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kimi_k2 as kk
from horovod_tpu.models import longcat_flash as lc
from horovod_tpu.models import transformer as tfm
from horovod_tpu.models.transformer import RopeScaling
from horovod_tpu.serving import Request, ServeEngine, ServeScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import lowprec                          # noqa: E402
from benchmarks.reference import kimi_k2 as ref             # noqa: E402
# the engine's own program calls with their logits, and the compiled text's
# normalisation, as the LongCat tests read them
from test_longcat_flash import (                            # noqa: E402
    SMALL as LC_SMALL, _decode_logits, _normal, _prefill_logits)

# YaRN at a small factor over 16 rotary dimensions: the ramp runs over pairs
# 0..3 of 8, so some pairs keep their frequency, some are blended, some slowed
TOY_YARN = RopeScaling(factor=4.0, original_max_position=64, beta_fast=4.0,
                       beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
SMALL = dict(vocab_size=128, d_model=64, n_layers_total=3, first_k_dense=1,
             d_ff=96, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_dim=16, qk_rope_dim=16, v_dim=16, n_routed_experts=32,
             top_k=4, routed_scaling=2.827, d_expert=32, d_shared=32,
             rope_theta=10000.0, rope_scaling=TOY_YARN, max_seq=128)


def _cfg(**kw):
    return kk.KimiK2Config(**{**SMALL, "dtype": jnp.float32, **kw})


def _dims(cfg):
    y = cfg.rope_scaling
    return ref.Dims(
        heads=cfg.n_heads, nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim,
        v=cfg.v_dim, kv_rank=cfg.kv_lora_rank, q_rank=cfg.q_lora_rank,
        n_routed=cfg.n_routed_experts, top_k=cfg.top_k,
        scaling=cfg.routed_scaling, first=cfg.expert_first,
        count=cfg.held_experts, theta=cfg.rope_theta, eps=cfg.norm_eps,
        dense_layers=cfg.first_k_dense,
        yarn=None if y is None else (
            y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim))


def _params(cfg, seed=1):
    """Seeded weights with every norm scale and routing bias off its
    neutral value, so none of them can be dropped unseen."""
    params = kk.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree.unflatten(treedef, [
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] != cfg.vocab_size
        and a.shape[0] != cfg.vocab_size else a
        for a, k in zip(leaves, keys)])


def _reference_logits(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            lowprec.F32, _dims(cfg), params, jnp.asarray(tokens),
            jnp.arange(len(tokens))))


def _engine(cfg, params, **kw):
    kw = {"slots": 2, "page": 8, "max_seq": 128, "prefill_chunk": 32,
          "prefix_cache": False, "draft": "off", **kw}
    return ServeEngine(cfg, params, None, **kw)


def test_engine_prefill_and_decode_through_the_latent_cache_match_the_reference():
    """Chunked prefill (32 + 32 + 6 tokens over pages of 8, past the 64
    positions the toy rotary was trained at) and then decode, logits against
    the reference's one full pass; the share held is experts 8..19 of 32."""
    cfg = _cfg(expert_first=8, expert_count=12)
    params = _params(cfg)
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 70).astype(np.int32)
    slot = eng.reserve(90)
    seq = list(prompt)
    chunks = _prefill_logits(eng, slot, prompt)
    assert [row for row, _, _ in chunks] == [31, 63, 69]
    got = {row: lg for row, _, lg in chunks}
    token = chunks[-1][1]
    for _ in range(6):
        seq.append(token)
        toks = np.zeros((eng.slots,), np.int32)
        toks[slot] = token
        nxt, logits = _decode_logits(eng, toks)
        got[len(seq) - 1] = logits[slot]
        token = int(nxt[slot])
    want = _reference_logits(cfg, params, np.array(seq, np.int32))
    for row, lg in got.items():
        np.testing.assert_allclose(lg, want[row], atol=2e-4, rtol=2e-4)
    # the pool is the model's: one array of latent rows, one block a layer
    assert [a.shape for a in eng.pools] == [
        (cfg.n_layers, eng.pool.n_pages + 1, 8, cfg.cache_row)]
    assert cfg.cache_row == cfg.kv_lora_rank + cfg.qk_rope_dim
    # the stretched rotary and its softmax factor are in what was compared:
    # the same weights under the plain rotary give other logits
    plain = dataclasses.replace(cfg, rope_scaling=None)
    other = _reference_logits(plain, params, np.array(seq, np.int32))
    assert np.abs(other[-1] - want[-1]).max() > 1e-2


def test_scheduler_serves_it_like_any_model():
    """``ServeScheduler`` unchanged: requests of mixed lengths through
    admission, chunked prefill and batched decode give the tokens the
    reference's greedy continuation gives."""
    cfg = _cfg(expert_first=20, expert_count=12)
    params = _params(cfg, seed=2)
    eng = _engine(cfg, params, slots=3)
    sched = ServeScheduler(eng)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 19, 75, 9)]
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
    for _ in range(200):
        sched.step()
        if len(sched.completed) == len(prompts):
            break
    assert len(sched.completed) == len(prompts)
    for req in sched.completed:
        assert req.error is None and len(req.tokens) == 5
        seq = list(req.prompt) + list(req.tokens)
        want = _reference_logits(cfg, params, np.array(seq[:-1], np.int32))
        n = len(req.prompt)
        assert list(req.tokens) == [
            int(np.argmax(want[n - 1 + i])) for i in range(5)]


# ---------------------------------------------------------------------------
# the expert layer's shares (guide section 4)
# ---------------------------------------------------------------------------

def _moe_inputs(cfg, rows=10, seed=3):
    ep = jax.tree.map(lambda a: a[0], _params(cfg, seed)["layers"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(seed), (rows, cfg.d_model))
    return ep, h


def _held(ep, first, count):
    sl = slice(first, first + count)
    return {**ep, **{k: ep[k][sl] for k in ("w_gate", "w_up", "w_down")}}


def _program_half(cfg, ep, h, first, count, shared=True):
    """What the program's expert half adds to h for the share [first, first
    + count) (the shared expert's term left out where ``shared`` is off)."""
    from horovod_tpu.models import granite_hybrid as stack
    share = dataclasses.replace(cfg, expert_first=first, expert_count=count)
    part = _held(ep, first, count)
    if not shared:
        part = {**part, "shared": jax.tree.map(jnp.zeros_like,
                                               part["shared"])}
    with jax.default_matmul_precision("highest"):
        out, _ = stack.experts(share, part, h, None)
    return np.asarray(out - h)


def _reference_half(cfg, ep, h, first, count, shared=True):
    dims = dataclasses.replace(_dims(cfg), first=first, count=count)
    with jax.default_matmul_precision("highest"):
        y = ref.rmsnorm(h, ep["norm"], dims.eps)
        return np.asarray(ref.moe(lowprec.F32, dims, y,
                                  _held(ep, first, count), shared=shared))


@pytest.mark.parametrize("half", [_program_half, _reference_half],
                         ids=["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(half):
    """Guide section 4: the routed parts of the shares [0, 12), [12, 24),
    [24, 32) of the 32 experts plus the shared expert counted once = the
    layer with every expert held."""
    cfg = _cfg()
    ep, h = _moe_inputs(cfg)
    n = cfg.n_routed_experts
    whole = half(cfg, ep, h, 0, n)
    shares = [(0, 12), (12, 12), (24, 8)]
    routed = sum(half(cfg, ep, h, first, count, shared=False)
                 for first, count in shares)
    shared_once = (half(cfg, ep, h, 0, 12)
                   - half(cfg, ep, h, 0, 12, shared=False))
    assert np.abs(shared_once).max() > 0.01
    assert np.abs(routed).max() > 0.01
    np.testing.assert_allclose(routed + shared_once, whole, atol=2e-5,
                               rtol=2e-5)
    # and the program's share is the reference's
    np.testing.assert_allclose(_program_half(cfg, ep, h, 12, 12),
                               _reference_half(cfg, ep, h, 12, 12),
                               atol=2e-5, rtol=2e-5)


def test_the_router_is_sigmoid_top_k_renormalised_and_scaled():
    cfg = _cfg()
    ep, h = _moe_inputs(cfg, rows=16)
    routing = cfg.route(h, ep)
    s = jax.nn.sigmoid(jnp.dot(h, ep["router"],
                               precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(s + ep["router_bias"], cfg.top_k)
    np.testing.assert_array_equal(np.asarray(routing.experts),
                                  np.asarray(want))
    np.testing.assert_allclose(np.asarray(routing.gates.sum(-1)),
                               cfg.routed_scaling, rtol=1e-6)


# ---------------------------------------------------------------------------
# the rotary: YaRN's frequencies, and the plain one bit for bit as it was
# ---------------------------------------------------------------------------

def _yarn_by_the_formula(theta, dim, factor, original, beta_fast,
                         beta_slow):
    """DeepSeek-V3's YarnRotaryEmbedding, line by line, in float64."""
    def correction(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    extra = [1.0 / theta ** (2 * i / dim) for i in range(dim // 2)]
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        mask = 1.0 - ramp
        out.append(f / factor * (1 - mask) + f * mask)
    return low, high, np.array(out)


def test_yarn_frequencies_and_mscale_at_the_published_numbers():
    """Kimi-K2's rope_scaling (yarn, factor 64 over 4096, beta 32 / 1,
    theta 50000, 64 rotary dimensions): low = floor(8.91) = 8, high =
    ceil(19.16) = 20; pairs 0-7 keep their frequency, 20-31 are divided by
    64; the softmax scale is 192^-1/2 * (0.1 ln 64 + 1)^2 = 0.14468."""
    y = kk.KIMI_K2_YARN
    low, high, want = _yarn_by_the_formula(50000.0, 64, 64.0, 4096, 32.0,
                                           1.0)
    assert (low, high) == (8, 20) == y.ramp(50000.0, 64)
    got = y.inv_freq(50000.0, 64)
    assert got.dtype == np.float32 and got.shape == (32,)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = 1.0 / 50000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:8], plain[:8], rtol=2e-6)
    np.testing.assert_allclose(got[20:], plain[20:] / 64, rtol=2e-6)
    assert np.all(got[9:20] < plain[9:20]) and np.all(
        got[9:20] > plain[9:20] / 64)
    assert y.softmax_factor == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    assert y.cos_sin_scale == 1.0
    cfg = kk.KimiK2Config()
    assert cfg.softmax_scale == pytest.approx(0.14468, abs=1e-5)
    # the reference's own transcription gives the same frequencies
    dims = _dims(cfg)
    assert dims.yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    np.testing.assert_allclose(ref.inv_freq(dims), got, rtol=1e-6)
    assert ref.softmax_scale(dims) == pytest.approx(cfg.softmax_scale)


def test_yarn_rope_rotates_by_the_stretched_frequencies():
    """``rope(.., scaling=)`` turns pair i by pos * inv_freq_i, times the
    cos / sin factor where mscale and mscale_all_dim differ."""
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 3, 16), jnp.float32)
    pos = jnp.arange(40, dtype=jnp.int32) * 37
    for y in (TOY_YARN, dataclasses.replace(TOY_YARN, mscale=0.7)):
        got = np.asarray(tfm.rope(x, pos, 10000.0, scaling=y))
        f = y.inv_freq(10000.0, 16).astype(np.float64)
        ang = np.asarray(pos, np.float64)[:, None, None] * f
        m = y.cos_sin_scale
        x1, x2 = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
        want = np.stack([x1 * np.cos(ang) * m - x2 * np.sin(ang) * m,
                         x1 * np.sin(ang) * m + x2 * np.cos(ang) * m],
                        axis=-1).reshape(x.shape)
        np.testing.assert_allclose(got, want, atol=2e-4)
    assert TOY_YARN.cos_sin_scale == 1.0
    assert dataclasses.replace(TOY_YARN, mscale=0.7).cos_sin_scale < 1.0


def _rope_as_it_was(x, pos, theta=10000.0, heads=1):
    """``transformer.rope`` as it stood before it took a scaling (PR 38's
    tree), line for line."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    lead = x.ndim - 1 - heads - pos.ndim
    ones = (*range(lead), *range(x.ndim - 1 - heads, x.ndim - 1))
    cos, sin = (jax.lax.expand_dims(cos, ones),
                jax.lax.expand_dims(sin, ones))
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                    axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_rope_without_scaling_is_bitwise_what_it_was(dtype, jit):
    b, s, h, d = 2, 19, 4, 64
    x = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, d)).astype(dtype)
    pos = 4000 + jnp.arange(s, dtype=jnp.int32) * 11
    cases = [(x, pos, 10000.0, 1), (x[0], pos, 1e7, 1),
             (x[0, :, 0], pos, 50000.0, 0)]
    for args in cases:
        new = (jax.jit(tfm.rope, static_argnums=(2, 3)) if jit
               else tfm.rope)(*args)
        old = (jax.jit(_rope_as_it_was, static_argnums=(2, 3)) if jit
               else _rope_as_it_was)(*args)
        assert new.dtype == old.dtype == dtype
        np.testing.assert_array_equal(np.asarray(new.astype(jnp.float32)),
                                      np.asarray(old.astype(jnp.float32)))
    # and the lowered program is the same, instruction for instruction
    def lowered(fn):
        text = jax.jit(fn).lower(x, pos).as_text(debug_info=False)
        return re.sub(r"@\w+|jit_\w+", "", text)
    assert lowered(lambda a, p: tfm.rope(a, p)) == lowered(
        lambda a, p: _rope_as_it_was(a, p))


# ---------------------------------------------------------------------------
# the stack: a leading dense layer, then runs of expert layers
# ---------------------------------------------------------------------------

def test_the_stack_is_a_dense_layer_then_a_run_of_expert_layers():
    cfg = _cfg(n_layers_total=5, first_k_dense=1, expert_first=4,
               expert_count=12)
    assert cfg.layer_types == ("dense",) + ("moe",) * 4
    assert cfg.runs() == [("dense", 0, 0, 1), ("moe", 1, 0, 4)]
    assert cfg.attention_blocks == 5
    shapes = jax.eval_shape(lambda: kk.init_params(
        cfg, jax.random.PRNGKey(0)))
    layers = shapes["layers"]
    assert layers["mla"]["wq_b"].shape == (5, 24, 4 * 32)
    assert layers["dense"]["w_gate"].shape == (1, 64, 96)
    assert layers["moe"]["w_gate"].shape == (4, 12, 64, 32)
    assert layers["moe"]["router"].shape == (4, 64, 32)
    assert layers["moe"]["router"].dtype == jnp.float32
    assert layers["moe"]["shared"]["w_down"].shape == (4, 32, 64)
    # the routing counters count the expert layers' rows alone
    params = _params(cfg)
    eng = _engine(cfg, params)
    rng = np.random.default_rng(2)
    slot = eng.reserve(50)
    eng.prefill(slot, rng.integers(0, cfg.vocab_size, 37).astype(np.int32))
    for _ in range(3):
        eng.decode_step(np.zeros((eng.slots,), np.int32))
    s = eng.stats()["moe"]
    rows = 37 + 3
    assert (s["assignments_held"] + s["assignments_zero"]
            + s["assignments_absent"]) == rows * cfg.top_k * 4
    assert s["assignments_zero"] == 0
    assert sum(s["rows_per_expert"]) == s["assignments_held"] > 0
    assert len(s["rows_per_expert"]) == 12 and s["expert_first"] == 4
    # first_k_dense moves the run boundary; a stack of dense layers alone
    # is refused
    two = dataclasses.replace(cfg, first_k_dense=2)
    assert two.runs() == [("dense", 0, 0, 2), ("moe", 2, 0, 3)]
    with pytest.raises(ValueError, match="no expert layer"):
        _engine(dataclasses.replace(cfg, first_k_dense=5), params)


@pytest.mark.parametrize("draft", ["ngram:2", "truncate:1"])
def test_draft_modes_are_refused_for_this_model(draft):
    cfg = _cfg()
    with pytest.raises(ValueError, match="plain decode only"):
        _engine(cfg, _params(cfg), draft=draft, spec_k=2)


def test_weights_stay_in_the_dtype_given():
    """bfloat16 leaves stay bfloat16 on the device, and the decode step
    widens no weight stack: the only float32 products are the router's."""
    cfg = _cfg(dtype=jnp.bfloat16, expert_first=0, expert_count=12)
    params = kk.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(eng.params)[0]:
        name = jax.tree_util.keystr(path)
        if leaf.ndim >= 3 and "router" not in name:
            assert leaf.dtype == jnp.bfloat16, name
    jaxpr = jax.make_jaxpr(lambda *a: kk.decode_body(cfg, *a))(
        eng.params, *eng.pools, *eng.state,
        jnp.asarray(eng.tables.tables), jnp.asarray(eng.tables.lengths),
        jnp.zeros((eng.slots,), jnp.int32))
    widened = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "convert_element_type":
                a = eqn.invars[0].aval
                if (a.dtype == jnp.bfloat16 and a.ndim >= 2
                        and eqn.params["new_dtype"] == jnp.float32
                        and min(a.shape[-2:]) >= cfg.kv_lora_rank
                        and a.shape[0] != eng.slots):
                    widened.append(a)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert not widened, widened


# ---------------------------------------------------------------------------
# LongCat's programs, whose attention blocks and step moved to models/mla.py
# ---------------------------------------------------------------------------

def test_longcat_programs_are_unchanged_by_the_shared_latent_step():
    """LongCat's decode and prefill programs, compiled for a small config in
    bfloat16 on the CPU, against what the tree before ``models/mla.py``
    compiled (PR 38's; ``tests/data/serve_longcat_programs.json``): the same
    text once metadata and instruction names are taken out. (At the cell's
    real size, compile-only for a v5e, the decode and all four prefill
    buckets were compared the same way: CHANGES.md, PR 39.)"""
    with open(os.path.join(ROOT, "tests", "data",
                           "serve_longcat_programs.json")) as f:
        before = json.load(f)
    cfg = lc.LongCatFlashConfig(**{**LC_SMALL, "dtype": jnp.bfloat16,
                                   "expert_first": 2, "expert_count": 4})
    eng = ServeEngine(cfg, lc.init_params(cfg, jax.random.PRNGKey(0)), None,
                      slots=2, page=8, max_seq=64, prefill_chunk=32,
                      prefix_cache=False, draft="off")
    assert cfg.rope_scaling is None
    assert cfg.softmax_scale == (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    for label, was in before.items():
        text = _normal(eng.executable_text(label))
        ops = collections.Counter(
            m.group(1) for m in re.finditer(
                r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", text, re.M))
        assert dict(sorted(ops.items())) == was["opcodes"], label
        assert hashlib.sha256(text.encode()).hexdigest() == was["sha256"], \
            label
