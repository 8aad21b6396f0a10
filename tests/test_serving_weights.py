"""The served weights are cast once, when the engine is built
(``ServeModel.served_params``, docs/serving.md "Weights are cast once"):
the programs read a tree whose product weights are in ``cfg.dtype``
already, give the bits the per-step casts gave, and convert no float32
weight; a tree given in ``cfg.dtype`` passes through untouched."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import tracing as trace
from horovod_tpu.models import longcat_flash as lc
from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import ServeEngine, engine as eng_mod

CAST = ("embed", "head", "wq", "wk", "wv", "wo", "w_in", "w_out")
KEPT = ("final_norm", "attn_norm", "mlp_norm")
SLOTS, PAGE, CTX, BUCKET, SPEC_K = 4, 8, 64, 32, 2
PROGRAMS = {"decode": "serve_decode", "prefill": f"serve_prefill_{BUCKET}",
            "verify": f"serve_verify_k{SPEC_K}", "draft": "serve_draft_l1"}


def _cfg(**kw):
    base = dict(vocab_size=256, d_model=64, n_heads=4, head_dim=16,
                n_layers=2, d_ff=128, max_seq=CTX, dtype=jnp.bfloat16,
                dp_axis=None, remat=False)
    return tfm.TransformerConfig(**{**base, **kw})


def _params(cfg, seed=0):
    """float32 weights with the norm scales off 1 (a scale rounded to
    bfloat16 would then show)."""
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 7), 3))
    nudge = lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
    params["final_norm"] = nudge(params["final_norm"])
    for n in ("attn_norm", "mlp_norm"):
        params["layers"][n] = nudge(params["layers"][n])
    return params


def _engine(cfg, params, mesh=None, **kw):
    kw = {"slots": SLOTS, "page": PAGE, "max_seq": CTX,
          "prefill_chunk": BUCKET, "prefix_cache": False, "draft": "off",
          **kw}
    return ServeEngine(cfg, params, mesh, **kw)


def _cast_ahead(params, dtype):
    """The tree with the eight product weights in ``dtype`` already."""
    layers = params["layers"]
    return {**params,
            **{n: params[n].astype(dtype) for n in CAST[:2]},
            "layers": {**layers, **{n: layers[n].astype(dtype)
                                    for n in CAST[2:]}}}


def _named(tree):
    """leaf name -> leaf, of a dense tree."""
    return {**{k: v for k, v in tree.items() if k != "layers"},
            **tree["layers"]}


@pytest.fixture(scope="module")
def served():
    """(engine with every program built, the float32 tree it was given),
    two slots prefilled."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, params, draft="truncate:1", spec_k=SPEC_K)
    rng = np.random.default_rng(0)
    for n in (11, 19):
        eng.prefill(eng.reserve(n + 8), rng.integers(0, 256, n, np.int32))
    return eng, params


def _call(eng, program):
    """(the engine's compiled program, the body it was jitted from, the
    arguments after the pool) for one run on the engine's present state."""
    cfg, lengths = eng.cfg, eng.tables.lengths
    rng = np.random.default_rng(1)
    if program == "prefill":
        slot = eng.reserve(40)
        chunk = rng.integers(0, 256, BUCKET, np.int32)
        args = (jnp.asarray(eng.tables.tables[slot]), jnp.asarray(0),
                jnp.asarray(BUCKET - 3), jnp.asarray(chunk))
        eng.release(slot)
        return (eng._prefill[BUCKET],
                functools.partial(tfm.prefill_body, cfg), args)
    rows = SLOTS * (SPEC_K + 1) if program == "verify" else SLOTS
    rep = rows // SLOTS
    bt = np.repeat(eng.tables.tables, rep, axis=0)
    ln = (np.repeat(lengths, rep)
          + np.tile(np.arange(rep, dtype=np.int32), SLOTS))
    idle = np.repeat(lengths == 0, rep)
    bt[idle], ln[idle] = eng.pool.scratch_page, 0
    args = (jnp.asarray(bt), jnp.asarray(ln.astype(np.int32)),
            jnp.asarray(rng.integers(0, 256, rows, np.int32)))
    compiled = {"decode": eng._decode, "verify": eng._verify,
                "draft": eng._draft}[program]
    body = functools.partial(tfm.decode_body, cfg,
                             n_layers=1 if program == "draft" else None)
    return compiled, body, args


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_prepared_tree_gives_the_bits_of_the_per_step_casts(served, program):
    """Logits, tokens and the pool of each engine program equal, bit for
    bit, those of the same body jitted on the float32 tree."""
    eng, f32 = served
    compiled, body, args = _call(eng, program)
    pools = tuple(jnp.array(p) for p in eng.pools)      # the run donates
    want = jax.jit(body)(f32, *pools, *args)
    tokens, logits = eng._step(compiled, *args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want[3]))
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(want[2]))
    for got, ref in zip(eng.pools, want[:2]):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)))
    assert np.asarray(logits).dtype == np.float32
    assert np.abs(np.asarray(logits)).max() > 0


def _weight_converts(jaxpr, shapes):
    """``convert_element_type`` equations from float32 of an array of two
    axes or more whose shape is one of ``shapes``, nested jaxprs too."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "convert_element_type":
                a = eqn.invars[0].aval
                if (a.dtype == jnp.float32 and a.ndim >= 2
                        and tuple(a.shape) in shapes):
                    found.append((tuple(a.shape), eqn.params["new_dtype"]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_no_adopted_program_converts_a_float32_weight(served, program):
    eng, f32 = served
    shapes = set()
    for name, leaf in _named(f32).items():
        if leaf.ndim >= 2:
            shapes |= {tuple(leaf.shape), tuple(leaf.shape[1:])}
    shapes = {s for s in shapes if len(s) >= 2}
    rows = SLOTS * (SPEC_K + 1) if program == "verify" else SLOTS
    jit, args = {
        "prefill": (eng._prefill_jit, eng._prefill_args(BUCKET)),
        "draft": (eng._draft_jit, eng._decode_args()),
    }.get(program, (eng._decode_jit, eng._decode_args(rows)))
    assert PROGRAMS[program] in eng.store_outcomes
    assert _weight_converts(jax.make_jaxpr(jit)(*args), shapes) == []
    # the walk does find them where the body is handed the float32 tree
    given = (jax.tree.map(eng_mod._abstract, f32),) + tuple(args[1:])
    cast = _weight_converts(jax.make_jaxpr(jit)(*given), shapes)
    assert len(cast) == len(CAST) and all(
        dt == jnp.bfloat16 for _, dt in cast), cast


def test_named_leaves_are_cast_and_the_callers_tree_is_left_alone(served):
    eng, f32 = served
    got = _named(eng.params)
    assert set(got) == set(CAST) | set(KEPT)
    for name in CAST:
        assert got[name].dtype == jnp.bfloat16, name
        np.testing.assert_array_equal(
            np.asarray(got[name].astype(jnp.float32)),
            np.asarray(_named(f32)[name].astype(jnp.bfloat16)
                       .astype(jnp.float32)))
    for name in KEPT:
        assert got[name].dtype == jnp.float32, name
        assert got[name] is _named(f32)[name], name
    for name, leaf in _named(f32).items():
        assert not leaf.is_deleted() and leaf.dtype == jnp.float32, name
        assert np.isfinite(np.asarray(leaf)).all()
    nbytes = lambda t, names: sum(int(_named(t)[n].nbytes) for n in names)
    assert eng.stats()["weights"] == {
        "cast_leaves": len(CAST), "cast_from_bytes": nbytes(f32, CAST),
        "resident_bytes": nbytes(f32, CAST) // 2 + nbytes(f32, KEPT)}


@pytest.mark.parametrize("model", ["dense_bf16", "dense_f32", "longcat"])
def test_a_tree_in_the_served_dtype_passes_through(model):
    """Nothing to cast: every leaf the engine holds is the array it was
    given, and LongCat's router stays float32 beside bfloat16 stacks."""
    if model == "longcat":
        cfg = lc.LongCatFlashConfig(
            vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            d_expert=32, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16,
            qk_rope_dim=8, v_dim=16, n_routed_experts=8, n_zero_experts=4,
            top_k=3, routed_scaling=2.0, max_seq=CTX, dtype=jnp.bfloat16)
        params = lc.init_params(cfg, jax.random.PRNGKey(0))
        assert cfg.serve_model().served_params is None
    else:
        dt = jnp.bfloat16 if model == "dense_bf16" else jnp.float32
        cfg = _cfg(dtype=dt)
        params = _cast_ahead(_params(cfg), dt)
    eng = _engine(cfg, params, slots=2)
    given, held = jax.tree.leaves(params), jax.tree.leaves(eng.params)
    assert len(given) == len(held)
    assert all(h is g for g, h in zip(given, held))
    w = eng.stats()["weights"]
    assert w["cast_leaves"] == 0 and w["cast_from_bytes"] == 0
    assert w["resident_bytes"] == sum(int(a.nbytes) for a in given)
    if model == "longcat":
        dtypes = {jax.tree_util.keystr(p): a.dtype for p, a in
                  jax.tree_util.tree_flatten_with_path(eng.params)[0]}
        assert [d for n, d in dtypes.items() if "router" in n] \
            and all(d == jnp.float32 for n, d in dtypes.items()
                    if "router" in n)
        assert {d for n, d in dtypes.items() if n.endswith("['wq_a']")} \
            == {jnp.dtype(jnp.bfloat16)}
    slot = eng.reserve(12)
    eng.prefill(slot, np.arange(8, dtype=np.int32))


@pytest.mark.parametrize("placement", ["tp2", "replicated"])
def test_every_prepared_leaf_keeps_its_sharding(placement):
    devices = np.array(jax.devices()[:2])
    if placement == "tp2":
        mesh, cfg = Mesh(devices, ("tp",)), _cfg(tp_axis="tp")
        specs = tfm.param_specs(cfg)
    else:
        mesh, cfg = Mesh(devices, ("replica",)), _cfg()
        specs = jax.tree.map(lambda s: P(), tfm.param_specs(cfg),
                             is_leaf=lambda x: isinstance(x, P))
    f32 = _params(cfg)
    eng = _engine(cfg, f32, mesh)
    for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(eng.params)[0],
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        name = jax.tree_util.keystr(path)
        assert isinstance(leaf.sharding, NamedSharding), name
        assert leaf.sharding.is_equivalent_to(
            NamedSharding(mesh, spec), leaf.ndim), (name, leaf.sharding)
        assert leaf.committed, name
    assert eng.stats()["weights"]["cast_leaves"] == len(CAST)
    assert all(_named(eng.params)[n].dtype == jnp.bfloat16 for n in CAST)
    # and serves what the same mesh serves from a tree cast beforehand
    twin = _engine(cfg, _cast_ahead(f32, jnp.bfloat16), mesh)
    assert twin.stats()["weights"]["cast_leaves"] == 0
    prompt = np.arange(3, 16, dtype=np.int32)
    served = []
    for e in (eng, twin):
        slot = e.reserve(24)
        first = e.prefill(slot, prompt)
        served.append((first, e.decode_step(
            np.full((SLOTS,), first, np.int32)).tolist()[slot]))
    assert served[0] == served[1]


def test_load_for_serving_to_engine_casts_a_float32_snapshot(tmp_path):
    from horovod_tpu.parallel.trainer import TrainState
    from horovod_tpu.resilience import AsyncCheckpointer
    from horovod_tpu.serving import load_for_serving
    cfg = _cfg()
    trained = _params(cfg, seed=3)
    state = TrainState(jnp.asarray(5, jnp.int32), trained,
                       jax.tree.map(jnp.zeros_like, trained))
    d = str(tmp_path / "ckpt")
    with AsyncCheckpointer(d, interval=0, fmt="pickle") as ck:
        ck.save(5, state, sync=True)
    step, params = load_for_serving(d, mesh=None, cfg=cfg)
    assert step == 5
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    eng = _engine(cfg, params, slots=2)
    w = eng.stats()["weights"]
    assert w["cast_leaves"] == 8
    assert w["resident_bytes"] < w["cast_from_bytes"]
    # what it serves is what an engine on the trained tree serves
    prompt = np.arange(2, 14, dtype=np.int32)
    twin = _engine(cfg, trained, slots=2)
    assert (eng.prefill(eng.reserve(20), prompt)
            == twin.prefill(twin.reserve(20), prompt))


def test_engine_keeps_no_float32_leaf_it_replaced():
    """Given a host tree, the engine places it itself: once built, no
    float32 array the size of a weight stack is alive on the device, so
    a caller that drops its own device tree frees it."""
    cfg = _cfg()
    host = jax.tree.map(np.asarray, _params(cfg))
    smallest = min(int(_named(host)[n].nbytes) for n in CAST)
    before = {id(x) for x in jax.live_arrays()}
    eng = _engine(cfg, host, slots=2)
    left = [x for x in jax.live_arrays() if id(x) not in before
            and x.dtype == jnp.float32 and x.ndim >= 2
            and x.nbytes >= smallest]
    assert [(x.shape, x.dtype) for x in left] == []
    assert eng.stats()["weights"]["cast_leaves"] == len(CAST)


def test_preparation_runs_once_under_its_span_and_is_logged():
    import logging
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    cfg = _cfg()
    params = _params(cfg)
    log = logging.getLogger("horovod_tpu.serving")
    handler, level, was = _Capture(), log.level, trace.enabled()
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    trace.enable()
    spans = lambda: [s for s in trace.snapshot()
                     if s["name"] == "engine.weights.prepare"]
    try:
        n0 = len(spans())
        eng = _engine(cfg, params, slots=2)
        eng.prefill(eng.reserve(12), np.arange(8, dtype=np.int32))
        eng.decode_step(np.zeros((2,), np.int32))
        assert len(spans()) == n0 + 1
        assert spans()[-1]["cat"] == trace.CAT_SERVE
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
        if not was:
            trace.disable()
    up = [m for m in records if "serve engine up" in m]
    assert up and "8 leaves cast once" in up[-1], up
