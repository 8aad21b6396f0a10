"""The shortcut-MoE latent-attention model (``models/longcat_flash.py``)
through ``ServeEngine`` and ``ServeScheduler`` at a small size, against the
plain reference (``benchmarks/reference/longcat_flash.py``, which imports
nothing of the program); the top-k share layer of ``parallel/moe.py``; and
that the engine's dense programs came through the refactor unchanged."""

import collections
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as M
from horovod_tpu.models import longcat_flash as lc
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import moe
from horovod_tpu.serving import Request, ServeEngine, ServeScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import lowprec                          # noqa: E402
from benchmarks.reference import longcat_flash as ref       # noqa: E402

SMALL = dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
             d_expert=32, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16,
             qk_rope_dim=8, v_dim=16, n_routed_experts=8, n_zero_experts=4,
             top_k=3, routed_scaling=2.0, max_seq=64)


def _cfg(**kw):
    return lc.LongCatFlashConfig(**{**SMALL, "dtype": jnp.float32, **kw})


def _dims(cfg):
    return ref.Dims(
        heads=cfg.n_heads, nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim,
        v=cfg.v_dim, kv_rank=cfg.kv_lora_rank, q_rank=cfg.q_lora_rank,
        hidden=cfg.d_model, n_routed=cfg.n_routed_experts, top_k=cfg.top_k,
        scaling=cfg.routed_scaling, first=cfg.expert_first,
        count=cfg.held_experts, theta=cfg.rope_theta, eps=cfg.norm_eps)


def _params(cfg, seed=1):
    """Seeded weights with every norm scale and routing bias off its
    neutral value, so none of them can be dropped unseen."""
    params = lc.init_params(cfg, jax.random.PRNGKey(seed))
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
    kw = {"slots": 2, "page": 8, "max_seq": 64, "prefill_chunk": 32,
          "prefix_cache": False, "draft": "off", **kw}
    return ServeEngine(cfg, params, None, **kw)


def _prefill_logits(eng, slot, prompt):
    """``ServeEngine.prefill`` keeping each chunk's logits: the engine's own
    program calls, in its own order."""
    start, out = 0, []
    while start < len(prompt):
        n_real = min(len(prompt) - start, eng.bucket_for(len(prompt) - start))
        bucket = eng.bucket_for(n_real)
        chunk = np.zeros((bucket,), np.int32)
        chunk[:n_real] = prompt[start:start + n_real]
        tok, logits = eng._step(
            eng._prefill[bucket], jnp.asarray(eng.tables.tables[slot]),
            jnp.asarray(start, jnp.int32), jnp.asarray(n_real, jnp.int32),
            jnp.asarray(chunk))
        start += n_real
        out.append((start - 1, int(tok), np.asarray(logits)))
    eng.tables.lengths[slot] = len(prompt)
    return out


def _decode_logits(eng, tokens):
    nxt, logits = eng._step(
        eng._decode, jnp.asarray(eng.tables.tables),
        jnp.asarray(eng.tables.lengths),
        jnp.asarray(np.asarray(tokens, np.int32)))
    # read back BEFORE touching the host tables: the dispatch is
    # asynchronous and may alias the NumPy buffers it was given
    nxt, logits = np.asarray(nxt), np.asarray(logits)
    eng.tables.lengths[eng.tables.lengths > 0] += 1
    return nxt, logits


def test_engine_prefill_and_decode_through_the_latent_cache_match_the_reference():
    """Chunked prefill (32 + 5 tokens over pages of 8: chunk and page
    boundaries both crossed) and then decode, logits against the reference's
    one full pass; the share held is experts 2..5 of 8."""
    cfg = _cfg(expert_first=2, expert_count=4)
    params = _params(cfg)
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 37).astype(np.int32)
    slot = eng.reserve(50)
    seq = list(prompt)
    chunks = _prefill_logits(eng, slot, prompt)
    assert [row for row, _, _ in chunks] == [31, 36]
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
    # the pool is the model's: one array of latent rows, two blocks a layer
    assert [a.shape for a in eng.pools] == [
        (2 * cfg.n_layers, eng.pool.n_pages + 1, 8, cfg.cache_row)]
    assert eng.pool.nbytes() == int(np.prod(eng.pools[0].shape)) * 4
    assert eng.stats()["kv_pool_bytes"] == eng.pool.nbytes()


def test_scheduler_serves_it_like_any_model():
    """``ServeScheduler`` unchanged: requests of mixed lengths through
    admission, chunked prefill and batched decode give the tokens the
    reference's greedy continuation gives."""
    cfg = _cfg(expert_first=4, expert_count=4)
    params = _params(cfg, seed=2)
    eng = _engine(cfg, params, slots=3)
    sched = ServeScheduler(eng)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 19, 40, 9)]
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


def test_absorbed_decode_attention_equals_the_expanded_one():
    cfg = _cfg()
    bp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"]["mla"][1])
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    n, t = 5, 24
    q_nope = jax.random.normal(k1, (n, cfg.n_heads, cfg.qk_nope_dim))
    q_rope = jax.random.normal(k2, (n, cfg.n_heads, cfg.qk_rope_dim))
    rows = jax.random.normal(k3, (t, cfg.cache_row))
    visible = jnp.arange(t)[None, :] <= (jnp.arange(n)[:, None] + 11)
    with jax.default_matmul_precision("highest"):
        expanded = lc.mla_attend_expanded(cfg, bp, q_nope, q_rope, rows,
                                          visible)
        absorbed = lc.mla_attend_absorbed(
            cfg, bp, q_nope, q_rope, jnp.broadcast_to(rows, (n,) + rows.shape),
            visible)
    assert expanded.shape == (n, cfg.n_heads * cfg.v_dim)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-4, rtol=1e-4)


def _moe_inputs(cfg, rows=10, seed=3):
    mp = jax.tree.map(lambda a: a[0], _params(cfg, seed)["layers"]["moe"])
    u = jax.random.normal(jax.random.PRNGKey(seed), (rows, cfg.d_model))
    return mp, u


def _program_moe(cfg, mp, u, first, count, identity=True):
    with jax.default_matmul_precision("highest"):
        routing = moe.topk_route(u, mp["router"], mp["router_bias"],
                                 cfg.top_k, cfg.routed_scaling)
        if not identity:    # the routed part alone: no zero-compute expert
            routing = routing._replace(gates=jnp.where(
                routing.experts >= cfg.n_routed_experts, 0.0, routing.gates))
        sl = slice(first, first + count)
        return np.asarray(moe.expert_share_ffn(
            u, routing, mp["w_gate"][sl], mp["w_up"][sl], mp["w_down"][sl],
            n_routed=cfg.n_routed_experts, first=first))


def _reference_moe(cfg, mp, u, first, count, identity=True):
    import dataclasses
    dims = dataclasses.replace(_dims(cfg), first=first, count=count)
    sl = slice(first, first + count)
    share = {**mp, **{k: mp[k][sl] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.moe(lowprec.F32, dims, u, share,
                                  identity=identity))


@pytest.mark.parametrize("layer_of", [_program_moe, _reference_moe],
                         ids=["program", "reference"])
@pytest.mark.parametrize("count", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(layer_of, count):
    """Guide section 4: the routed parts of all n / count shares plus the
    zero-compute part counted once = the layer with every expert held."""
    cfg = _cfg()
    mp, u = _moe_inputs(cfg)
    n = cfg.n_routed_experts
    whole = layer_of(cfg, mp, u, 0, n)
    routed = sum(layer_of(cfg, mp, u, first, count, identity=False)
                 for first in range(0, n, count))
    identity_once = (layer_of(cfg, mp, u, 0, count)
                     - layer_of(cfg, mp, u, 0, count, identity=False))
    assert np.abs(identity_once).max() > 0.01      # some token chose one
    np.testing.assert_allclose(routed + identity_once, whole, atol=2e-5,
                               rtol=2e-5)
    # and the program's share is the reference's
    np.testing.assert_allclose(_program_moe(cfg, mp, u, count, count),
                               _reference_moe(cfg, mp, u, count, count),
                               atol=2e-5, rtol=2e-5)


def test_an_identity_expert_returns_its_gate_times_the_input():
    cfg = _cfg()
    mp, u = _moe_inputs(cfg)
    # the bias moves the choice onto the zero-compute experts, not the weight
    bias = jnp.where(jnp.arange(cfg.router_width) >= cfg.n_routed_experts,
                     10.0, 0.0)
    routing = moe.topk_route(u, mp["router"], bias, cfg.top_k,
                             cfg.routed_scaling)
    assert bool(jnp.all(routing.experts >= cfg.n_routed_experts))
    p = jax.nn.softmax(jnp.dot(u, mp["router"],
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    g = cfg.routed_scaling * jnp.take_along_axis(p, routing.experts, axis=-1)
    np.testing.assert_allclose(routing.gates, g, rtol=1e-6)
    out = moe.expert_share_ffn(u, routing, mp["w_gate"], mp["w_up"],
                               mp["w_down"], n_routed=cfg.n_routed_experts)
    np.testing.assert_allclose(out, jnp.sum(g, axis=-1, keepdims=True) * u,
                               rtol=1e-5, atol=1e-6)
    counts = moe.share_counts(routing, cfg.n_routed_experts, 0,
                              cfg.n_routed_experts)
    assert counts[:4].tolist() == [0, u.shape[0] * cfg.top_k, 0, 0]


def test_no_token_is_dropped_when_every_row_picks_the_same_held_expert():
    cfg = _cfg()
    mp, u = _moe_inputs(cfg, rows=48)
    bias = jnp.zeros((cfg.router_width,)).at[5].set(10.0)
    mp = {**mp, "router_bias": bias}
    routing = moe.topk_route(u, mp["router"], bias, cfg.top_k,
                             cfg.routed_scaling)
    assert bool(jnp.all(jnp.any(routing.experts == 5, axis=-1)))
    got = _program_moe(cfg, mp, u, 4, 2)
    want = _reference_moe(cfg, mp, u, 4, 2)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # every one of the 48 rows carries expert 5's term
    routed = _program_moe(cfg, mp, u, 5, 1, identity=False)
    assert np.all(np.abs(routed).max(axis=-1) > 0)
    counts = moe.share_counts(routing, cfg.n_routed_experts, 4, 2)
    assert int(counts[moe.N_SHARE_TOTALS + 1]) == 48


def test_routing_counters_add_up_to_rows_times_k():
    cfg = _cfg(expert_first=2, expert_count=4)
    eng = _engine(cfg, _params(cfg))
    rng = np.random.default_rng(2)
    rows = 0
    slots = []
    for n in (37, 6):
        slot = eng.reserve(n + 8)
        eng.prefill(slot, rng.integers(0, cfg.vocab_size, n).astype(np.int32))
        slots.append(slot)
        rows += n
    before = eng.stats()["moe"]
    assert (before["assignments_held"] + before["assignments_zero"]
            + before["assignments_absent"]) == rows * cfg.top_k * cfg.n_layers
    for _ in range(3):          # both slots decode; padding is not counted
        eng.decode_step(np.zeros((eng.slots,), np.int32))
        rows += 2
    eng.release(slots[1])
    eng.decode_step(np.zeros((eng.slots,), np.int32))   # one slot is empty
    rows += 1
    s = eng.stats()["moe"]
    assert (s["assignments_held"] + s["assignments_zero"]
            + s["assignments_absent"]) == rows * cfg.top_k * cfg.n_layers
    assert sum(s["rows_per_expert"]) == s["assignments_held"]
    assert len(s["rows_per_expert"]) == 4 and s["expert_first"] == 2
    assert 0 < s["experts_active"] <= 4 * cfg.n_layers * (2 * 4 + 4)
    # totals only grow, and the registry carries them
    assert all(s[k] >= before[k] for k in before if k.startswith("assign"))
    assert M.get_registry().get("hvd_serve_moe_assignments_held").value \
        == s["assignments_held"]
    # the low word carries into the high one
    assert s["decode"]["assignments_held"] + s["prefill"]["assignments_held"] \
        == s["assignments_held"]
    assert sum(s["decode"][k] for k in s["decode"] if k.startswith("assign")) \
        == 7 * cfg.top_k * cfg.n_layers
    # the low word carries into the high one, each program's own totals
    full = jnp.zeros((2, 2, 2), jnp.uint32).at[0, 1].set(
        jnp.array([2 ** 32 - 2, 5], jnp.uint32)).at[1, 1, 1].set(1)
    out = np.asarray(moe.add_share_counts(full, jnp.array([3, 4], jnp.int32),
                                    lc.PREFILL))
    assert out[:, 1].tolist() == [[1, 9], [1, 1]] and not out[:, 0].any()


def test_a_step_ahead_routes_no_row_that_no_request_keeps():
    """The scheduler enqueues a step before it has read the one before;
    a request still ends by count, so the device's routing counters (kept
    in the state the queued programs hand from one to the next) count
    exactly the rows of the direct loop that reads every step: prompt
    rows, and one row for each token after a request's first."""
    cfg = _cfg(expert_first=2, expert_count=4)
    params = _params(cfg)
    rng = np.random.default_rng(8)
    sizes = [(6, 1), (37, 4), (11, 7), (20, 3)]     # prompt tokens, cap
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in sizes]
    direct = _engine(cfg, params, slots=3)
    want = []
    for prompt, (_, cap) in zip(prompts, sizes):
        slot = direct.reserve(len(prompt) + cap)
        tokens = np.zeros((direct.slots,), np.int32)
        out = [direct.prefill(slot, prompt)]
        for _ in range(cap - 1):
            tokens[slot] = out[-1]
            out.append(int(direct.decode_step(tokens)[slot]))
        direct.release(slot)
        want.append(out)
    eng = _engine(cfg, params, slots=3)
    done = ServeScheduler(eng, queue_deadline=0.0).run(
        [Request(rid=i, prompt=p, max_new_tokens=cap)
         for i, (p, (_, cap)) in enumerate(zip(prompts, sizes))])
    assert [r.tokens for r in sorted(done, key=lambda r: r.rid)] == want
    got, ref_ = eng.stats(), direct.stats()
    for part in ("decode", "prefill"):
        for key in ("assignments_held", "assignments_zero",
                    "assignments_absent"):
            assert got["moe"][part][key] == ref_["moe"][part][key], (part, key)
    rows = sum(cap - 1 for _, cap in sizes)
    assert sum(got["moe"]["decode"][k] for k in got["moe"]["decode"]
               if k.startswith("assign")) == rows * cfg.top_k * cfg.n_layers
    # three slots for four requests: fewer steps than rows, all but the
    # first of a stretch enqueued ahead, and nothing left in flight
    assert got["decode"]["steps"] < rows
    assert got["decode"]["dispatched_ahead"] >= got["decode"]["steps"] - 2
    assert set(got["decode"]["drained"]) == {"idle"}
    assert ref_["decode"] == {"steps": rows, "dispatched_ahead": 0,
                              "drained": {"direct": rows}}
    assert eng._unread is None


@pytest.mark.parametrize("draft", ["ngram:2", "truncate:1"])
def test_draft_modes_are_refused_for_this_model(draft):
    cfg = _cfg()
    with pytest.raises(ValueError, match="plain decode only"):
        _engine(cfg, _params(cfg), draft=draft, spec_k=2)


def test_weights_stay_in_the_dtype_given():
    """bfloat16 leaves stay bfloat16 on the device, and the decode step
    widens no weight stack: the only float32 products are the router's."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params = lc.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(eng.params)[0]:
        name = jax.tree_util.keystr(path)
        if leaf.ndim >= 3 and "router" not in name:
            assert leaf.dtype == jnp.bfloat16, name
    jaxpr = jax.make_jaxpr(lambda *a: lc.decode_body(cfg, *a))(
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


def _normal(text):
    """A compiled text without what moves with a source line, a core count
    or the order in which the tracer made the instructions: the tables of
    files and stack frames, metadata, backend configuration, and every
    instruction's and computation's name replaced by its rank of first
    appearance (``%mul.394`` and ``%mul.430`` of two traces of one
    program are both ``%v17``)."""
    out = []
    for line in text.splitlines():
        if re.match(r"^(\d+ |FileNames|FunctionNames|FileLocations|"
                    r"StackFrames)", line):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        line = re.sub(r", backend_config=\{.*\}$", "", line)
        line = re.sub(r", frontend_attributes=\{[^}]*\}", "", line)
        out.append(line.rstrip())
    names = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  "\n".join(out))


def test_dense_programs_are_unchanged_by_the_model_described_pool():
    """The dense block's decode, prefill and copy-on-write programs, compiled
    for a small config on the CPU, against what the tree that gave the dense
    pool its flat row (PR 34's: a page is ``[page, KVH * D]``) compiled: the
    same text once source lines and instruction names are taken out, so the
    same pool arrays, the same layout pin and no new instruction. (Until
    then the readings were PR 30's, and PR 31 and 33 moved the bodies under
    them without moving a character.)
    ``tests/data/serve_dense_programs.json`` holds that tree's readings."""
    with open(os.path.join(ROOT, "tests", "data",
                           "serve_dense_programs.json")) as f:
        before = json.load(f)
    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, head_dim=16, n_layers=2,
        d_ff=128, max_seq=64, dtype=jnp.float32, dp_axis=None)
    eng = ServeEngine(cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), None,
                      slots=2, page=8, max_seq=64, prefill_chunk=32,
                      prefix_cache=True, draft="off")
    assert [a.shape for a in eng.pools] == [(2, 17, 8, 4 * 16)] * 2
    assert eng.k_pages is eng.pools[0] and eng.v_pages is eng.pools[1]
    assert eng.state == ()
    for label, was in before.items():
        text = _normal(eng.executable_text(label))
        ops = collections.Counter(
            m.group(1) for m in re.finditer(
                r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", text, re.M))
        assert dict(sorted(ops.items())) == was["opcodes"], label
        assert hashlib.sha256(text.encode()).hexdigest() == was["sha256"], \
            label


# ---------------------------------------------------------------------------
# the one rotary (models/transformer.py: training, the dense serve bodies
# and this model's q_rope / k_r all call it)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_rope_on_a_batch_is_rope_row_by_row_bitwise(dtype):
    """What training rotates (``[B, S, H, D]`` with one ``pos[S]``) and what
    a decode step or a prefill chunk rotates (``[N, H, D]`` with a position
    a row) are the same bits: a cached key is the key training saw."""
    b, s, h, d = 3, 17, 4, 16
    x = jax.random.normal(jax.random.PRNGKey(5), (b, s, h, d)).astype(dtype)
    pos = 1000 + jnp.arange(s, dtype=jnp.int32)
    batch = tfm.rope(x, pos)
    rows = tfm.rope(x.reshape(b * s, h, d), jnp.tile(pos, b))
    assert batch.dtype == rows.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(batch.astype(jnp.float32)).reshape(b * s, h, d),
        np.asarray(rows.astype(jnp.float32)))
    # ... and a key without heads is the one-head key
    np.testing.assert_array_equal(
        np.asarray(tfm.rope(x[0, :, 0], pos, heads=0).astype(jnp.float32)),
        np.asarray(batch[0, :, 0].astype(jnp.float32)))
    assert np.any(np.asarray(batch != x))


def test_rope_of_a_headless_key_equals_the_reference_at_its_theta():
    n, d, theta = 33, 8, 1e7
    k_r = jax.random.normal(jax.random.PRNGKey(6), (n, d), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32) * 97           # up to 3104
    got = tfm.rope(k_r, pos, theta, heads=0)
    want = ref.rope(k_r[:, None, :], pos, theta)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=4 * np.finfo(np.float32).eps)
    # theta matters: the dense model's default is another rotation
    assert np.abs(np.asarray(tfm.rope(k_r, pos, heads=0) - got)).max() > 0.1
