"""Pallas flash-attention kernel tests, run in interpreter mode on the CPU
mesh (the TPU-hardware-free correctness substrate). The jnp implementation
``_block_attend`` is the behavioral spec."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas import flash_attention as fa
from horovod_tpu.parallel import sequence as sp


def reference(q, k, v, qoff, koff, causal, scale):
    return sp._block_attend(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), qoff, koff, causal,
                            scale)


def rand_qkv(rng, b, sq, sk, h, d):
    q = rng.standard_normal((b, sq, h, d)).astype(np.float32)
    k = rng.standard_normal((b, sk, h, d)).astype(np.float32)
    v = rng.standard_normal((b, sk, h, d)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 256), (256, 128)])
def test_flash_matches_reference(causal, sq, sk):
    rng = np.random.default_rng(0)
    q, k, v = rand_qkv(rng, b=2, sq=sq, sk=sk, h=2, d=64)
    scale = 64 ** -0.5
    o, m, l = fa.flash_block_attend(q, k, v, 0, 0, causal=causal,
                                    scale=scale, interpret=True)
    o_ref, m_ref, l_ref = reference(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), 0, 0, causal, scale)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-4,
                               atol=1e-4)


def test_flash_with_offsets_matches_reference():
    """Ring-step positioning: K block sits *after* Q in the global
    sequence -> fully masked under causal; and before -> fully visible."""
    rng = np.random.default_rng(1)
    q, k, v = rand_qkv(rng, b=1, sq=128, sk=128, h=1, d=64)
    scale = 0.125
    for qoff, koff in [(0, 128), (128, 0), (256, 128)]:
        o, m, l = fa.flash_block_attend(q, k, v, qoff, koff, causal=True,
                                        scale=scale, interpret=True)
        o_ref, m_ref, l_ref = reference(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), qoff, koff, True,
                                        scale)
        np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=1e-4, atol=1e-4)


def test_flash_traced_offsets_work_under_jit():
    """Offsets are traced scalars in ring attention (axis_index * S)."""
    rng = np.random.default_rng(2)
    q, k, v = rand_qkv(rng, b=1, sq=128, sk=128, h=1, d=64)

    @jax.jit
    def run(qoff):
        return fa.flash_block_attend(q, k, v, qoff, 0, causal=True,
                                     scale=0.125, interpret=True)

    o, m, l = run(jnp.asarray(128, jnp.int32))
    o_ref, _, l_ref = reference(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), 128, 0, True, 0.125)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-4,
                               atol=1e-4)


def test_supports_gates_shapes():
    rng = np.random.default_rng(3)
    q, k, _ = rand_qkv(rng, 1, 100, 128, 1, 64)     # Sq not divisible
    assert not fa.supports(jnp.asarray(q), jnp.asarray(k))
    q, k, _ = rand_qkv(rng, 1, 128, 128, 1, 64)
    assert fa.supports(jnp.asarray(q), jnp.asarray(k))
    # Long K streams by blocks — supported (no whole-K VMEM residency).
    q2 = jnp.zeros((1, 128, 1, 128), jnp.float32)
    k2 = jnp.zeros((1, 1 << 15, 1, 128), jnp.float32)
    assert fa.supports(q2, k2)
    # Head dim between lanes and 2*lanes breaks the lane tiling.
    q3 = jnp.zeros((1, 128, 1, 192), jnp.float32)
    assert not fa.supports(q3, q3)


def test_dispatcher_disabled_on_cpu_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_TPU_PALLAS", raising=False)
    assert fa.enabled() in (None, True)      # cpu -> None; tpu -> True
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "0")
    assert fa.enabled() is None
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    assert fa.enabled() in ("interpret", True)


def test_ring_attention_with_flash_interpret(monkeypatch, hvd_ctx):
    """End-to-end: ring attention over the 8-chip mesh with the kernel in
    interpret mode equals single-device full attention."""
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.eager import shard_map

    n = hvd.size()
    b, s, h, d = 1, 128 * n, 2, 64
    rng = np.random.default_rng(4)
    q, k, v = rand_qkv(rng, b, s, s, h, d)
    mesh = hvd.mesh()
    axis = mesh.axis_names[0]

    ring = shard_map(
        lambda q_, k_, v_: sp.ring_attention(q_, k_, v_, axis, causal=True),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis))
    out = ring(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    full = sp.local_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def full_attention_ref(q, k, v, causal, scale):
    """Dense softmax attention (normalized) — grad-checkable spec."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = np.tril(np.ones((sq, sk), bool))
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward_and_grads_match_dense(causal):
    """The differentiable entry: values AND all three input grads must
    match dense attention (interpret mode)."""
    rng = np.random.default_rng(7)
    q, k, v = rand_qkv(rng, b=1, sq=128, sk=256, h=2, d=64)
    scale = 64 ** -0.5
    q, k, v = map(jnp.asarray, (q, k, v))

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, causal, scale, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(full_attention_ref(q, k, v, causal, scale)))

    o_flash = fa.flash_attention(q, k, v, causal, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(o_flash),
                               np.asarray(full_attention_ref(q, k, v,
                                                             causal, scale)),
                               rtol=1e-4, atol=1e-4)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_local_attention_dispatches_flash_and_trains(monkeypatch):
    """local_attention (the transformer/Ulysses path) must use the
    differentiable kernel when forced and produce finite grads."""
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(8)
    q, k, v = map(jnp.asarray, rand_qkv(rng, 1, 128, 128, 2, 64))

    def loss(q):
        return jnp.sum(sp.local_attention(q, k, v, causal=True) ** 2)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    # And it matches the jnp fallback exactly in value.
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "0")
    o_fallback = sp.local_attention(q, k, v, causal=True)
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    o_flash = sp.local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_flash), np.asarray(o_fallback),
                               rtol=1e-4, atol=1e-4)


def test_local_attention_traced_scale_falls_back(monkeypatch):
    """A traced scale cannot reach the static-kernel path; must not crash."""
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(9)
    q, k, v = map(jnp.asarray, rand_qkv(rng, 1, 128, 128, 1, 64))
    out = jax.jit(
        lambda q, k, v, s: sp.local_attention(q, k, v, causal=True, scale=s)
    )(q, k, v, jnp.float32(0.125))
    ref = sp.local_attention(q, k, v, causal=True, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mismatched_v_shape_falls_back(monkeypatch):
    """d_v != d_qk is outside the kernel's contract — jnp path must serve
    it correctly (supports() gates on v)."""
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.standard_normal((1, 128, 1, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 1, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 1, 64)), jnp.float32)
    assert not fa.supports(q, k, v)
    out = sp.local_attention(q, k, v, causal=True)
    assert out.shape == (1, 128, 1, 64)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("flash", ["interpret", "0"])
def test_ring_attention_grads_match_full_attention(monkeypatch, hvd_ctx,
                                                   flash):
    """Ring attention's custom-VJP backward (pallas kernels or jnp blocks)
    must produce the same q/k/v grads as dense full attention."""
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", flash)
    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.eager import shard_map

    n = hvd.size()
    b, s, h, d = 1, 128 * n, 2, 64
    rng = np.random.default_rng(11)
    q, k, v = map(jnp.asarray, rand_qkv(rng, b, s, s, h, d))
    mesh = hvd.mesh()
    axis = mesh.axis_names[0]
    scale = d ** -0.5

    # jitted: an eager shard_map dispatches the ring op by op
    ring = jax.jit(shard_map(
        lambda q_, k_, v_: sp.ring_attention(q_, k_, v_, axis, causal=True),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis)))

    def loss_ring(q, k, v):
        return jnp.sum(jnp.sin(ring(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(full_attention_ref(q, k, v, True, scale)))

    np.testing.assert_allclose(
        np.asarray(ring(q, k, v)),
        np.asarray(full_attention_ref(q, k, v, True, scale)),
        rtol=2e-3, atol=2e-3)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gr, gf, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch ({flash})")


def test_ring_attention_traced_scale_falls_back(monkeypatch, hvd_ctx):
    """A traced scale must route to the plain (jnp) ring path end-to-end,
    including inside _ring_fwd_scan's flash gate."""
    monkeypatch.setenv("HOROVOD_TPU_PALLAS", "interpret")
    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.eager import shard_map

    n = hvd.size()
    rng = np.random.default_rng(12)
    q, k, v = map(jnp.asarray, rand_qkv(rng, 1, 128 * n, 128 * n, 1, 64))
    mesh = hvd.mesh()
    axis = mesh.axis_names[0]

    def with_scale(q_, k_, v_, s_):
        return sp.ring_attention(q_, k_, v_, axis, causal=True, scale=s_)

    ring = shard_map(with_scale, mesh,
                     in_specs=(P(None, axis), P(None, axis), P(None, axis),
                               P()),
                     out_specs=P(None, axis))
    out = jax.jit(ring)(q, k, v, jnp.float32(0.125))
    ref = sp.local_attention(q, k, v, causal=True, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("qoff,koff", [(0, 0), (128, 0), (0, 128),
                                       (256, 128)])
def test_flash_bwd_block_matches_jnp_spec_with_offsets(qoff, koff):
    """Direct unit coverage of the ring-backward building block: the
    pallas dq/dkv kernels must equal the jnp spec for every offset
    geometry (behind/ahead/aligned K blocks)."""
    rng = np.random.default_rng(13)
    b, sq, sk, h, d = 1, 128, 128, 2, 64
    q, k, v = map(jnp.asarray, rand_qkv(rng, b, sq, sk, h, d))
    do = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    scale = d ** -0.5
    # Global stats from a wider context (simulating mid-ring state).
    lse = jnp.asarray(rng.standard_normal((b, h, sq)) + 3.0, jnp.float32)
    dD = jnp.asarray(rng.standard_normal((b, h, sq)), jnp.float32)

    got = fa.flash_bwd_block(q, k, v, do, lse, dD, qoff, koff,
                             causal=True, scale=scale, interpret=True)
    want = sp._bwd_block_jnp(q, k, v, do, lse, dD, qoff, koff,
                             causal=True, scale=scale)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name} at ({qoff},{koff})")


def test_fit_block_keeps_non_default_sequences_eligible():
    """Raising the default blocks to 512/1024 must NOT drop sequences the
    old 128/256 defaults handled to the full-scores jnp path: blocks
    shrink to the largest aligned divisor (round-5 review regression)."""
    from horovod_tpu.ops.pallas.flash_attention import _fit_block, supports
    assert _fit_block(768, 512, 8) == 384
    assert _fit_block(1536, 1024, 128) == 768
    assert _fit_block(2560, 1024, 128) == 640
    assert _fit_block(100, 512, 128) is None
    q = jnp.zeros((1, 768, 4, 64), jnp.float32)
    try:
        from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    except ImportError:
        return                       # supports() is False without pltpu
    assert supports(q, q, q)


# -- PR 36: operand-dtype products, the three kinds of grid step, two
# finalisations, statistics as rows -------------------------------------------

def rand_bf16(seed, b, sq, sk, h, d):
    rng = np.random.default_rng(seed)
    q, k, v = rand_qkv(rng, b, sq, sk, h, d)
    do = rng.standard_normal((b, sq, h, d)).astype(np.float32)
    return tuple(jnp.asarray(x, jnp.bfloat16) for x in (q, k, v, do))


def gap(got, want):
    """Norm of the difference over the norm of the specification."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# S 512 in blocks of 128: 6 grid steps a head are skipped, 4 masked and 6
# run the body without a mask (test_causal_block_census pins it).
S_MIXED, BLK = 512, 128
# bfloat16 operands, float32 accumulation: a product's terms are rounded to
# 2^-9, sums over 64 to 512 terms; a wrong mask or a wrong body moves a
# result by its own size
BF16_GAP = 0.01


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_matches_jnp_spec(causal):
    """bfloat16 q, k, v, dO through the whole-softmax entry: forward and all
    three gradients against the jnp specification (``_block_attend``
    normalised; ``_bwd_block_jnp``) where every kind of grid step occurs."""
    q, k, v, do = rand_bf16(20, 1, S_MIXED, S_MIXED, 2, 64)
    scale = 64 ** -0.5
    o, vjp = jax.vjp(lambda q_, k_, v_: fa.flash_attention(
        q_, k_, v_, causal, scale, BLK, BLK, True), q, k, v)
    grads = vjp(do)
    assert o.dtype == jnp.bfloat16
    assert all(g.dtype == jnp.bfloat16 for g in grads)

    o_un, m, l = reference(q, k, v, 0, 0, causal, scale)
    o_spec = o_un / jnp.moveaxis(l, 1, -1)[..., None]
    lse = m + jnp.log(l)
    dD = jnp.sum(do.astype(jnp.float32) * o_spec, axis=-1).transpose(0, 2, 1)
    want = sp._bwd_block_jnp(q, k, v, do, lse, dD, 0, 0, causal, scale)
    assert gap(o, o_spec) < BF16_GAP
    for g, w, name in zip(grads, want, ("dq", "dk", "dv")):
        assert gap(g, w) < BF16_GAP, name


OFFSETS = [(0, 0), (256, 0), (0, 256), (512, 0), (0, 512)]


@pytest.mark.parametrize("qoff,koff", OFFSETS)
def test_flash_block_attend_bf16_with_offsets(qoff, koff):
    """The partial block's entry in bfloat16: unnormalised float32 ``o``,
    ``m`` and ``l`` as ``_block_attend`` gives them, for a shard on the
    diagonal, partly and wholly under it, partly and wholly above it."""
    q, k, v, _ = rand_bf16(21, 1, S_MIXED, S_MIXED, 2, 64)
    o, m, l = fa.flash_block_attend(q, k, v, qoff, koff, causal=True,
                                    scale=0.125, block_q=BLK, block_k=BLK,
                                    interpret=True)
    o_ref, m_ref, l_ref = reference(q, k, v, qoff, koff, True, 0.125)
    assert o.dtype == jnp.float32 and o.shape == q.shape
    assert m.shape == l.shape == (1, 2, S_MIXED)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref), rtol=1e-5,
                               atol=1e-5)
    if np.asarray(l_ref).any():
        assert gap(o, o_ref) < BF16_GAP
    else:                                    # wholly above the diagonal
        assert not np.asarray(o).any()


@pytest.mark.parametrize("qoff,koff", OFFSETS)
def test_flash_bwd_block_bf16_with_offsets(qoff, koff):
    """The ring's backward block in bfloat16 against the jnp specification:
    float32 gradients, global statistics from a wider context."""
    q, k, v, do = rand_bf16(22, 1, S_MIXED, S_MIXED, 2, 64)
    rng = np.random.default_rng(23)
    lse = jnp.asarray(rng.standard_normal((1, 2, S_MIXED)) + 4.0, jnp.float32)
    dD = jnp.asarray(rng.standard_normal((1, 2, S_MIXED)), jnp.float32)
    got = fa.flash_bwd_block(q, k, v, do, lse, dD, qoff, koff, causal=True,
                             scale=0.125, block_q=BLK, block_k=BLK,
                             interpret=True)
    want = sp._bwd_block_jnp(q, k, v, do, lse, dD, qoff, koff, True, 0.125)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == jnp.float32
        if np.asarray(w).any():
            assert gap(g, w) < BF16_GAP, f"{name} at ({qoff},{koff})"
        else:
            assert not np.asarray(g).any(), f"{name} at ({qoff},{koff})"


def _kernel_products(fn, *args):
    """{kernel name: [(lhs dtype, rhs dtype), ...]} of every ``dot_general``
    in the bodies of the ``pallas_call``s that ``fn(*args)`` traces."""
    found = {}

    def walk(jaxpr, into):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                walk(eqn.params["jaxpr"], found.setdefault(name, []))
                continue
            if eqn.primitive.name == "dot_general" and into is not None:
                into.append(tuple(str(x.aval.dtype) for x in eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, into)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_products_run_on_the_operands_dtype(dtype):
    """No product of the three kernel bodies takes an operand of another
    dtype than the call's: a bfloat16 call holds no float32 operand (the
    matrix unit would take it in several passes), a float32 call only
    float32 ones."""
    q, k, v, do = (x.astype(dtype) for x in rand_bf16(24, 1, 256, 256, 1, 64))

    def fwd_bwd(q_, k_, v_, do_):
        o, vjp = jax.vjp(lambda a, b, c: fa.flash_attention(
            a, b, c, True, 0.125, BLK, BLK, True), q_, k_, v_)
        return (o,) + vjp(do_)

    products = _kernel_products(fwd_bwd, q, k, v, do)
    # two bodies a kernel (masked and not): 2, 3 and 4 products each
    assert {n: len(p) for n, p in products.items()} == {
        "hvd_flash_fwd": 4, "hvd_flash_bwd_dq": 6, "hvd_flash_bwd_dkv": 8}
    for name, pairs in products.items():
        assert set(pairs) == {(dtype, dtype)}, (name, pairs)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_full_sequence_forward_writes_final_results(dtype):
    """The whole-softmax forward returns ``o`` in the input dtype and ONE
    ``[B, H, S]`` statistic, ``m + log l`` of the partial block's entry on
    the same inputs, and ``o`` is that entry's ``o / l``."""
    q, k, v, _ = (x.astype(dtype) for x in rand_bf16(25, 2, 256, 256, 2, 64))
    o, res = fa._flash_attention_fwd(q, k, v, True, 0.125, BLK, BLK, True)
    lse = res[-1]
    assert o.dtype == q.dtype and o.shape == q.shape
    assert lse.dtype == jnp.float32 and lse.shape == (2, 2, 256)
    o_un, m, l = fa.flash_block_attend(q, k, v, 0, 0, causal=True,
                                       scale=0.125, block_q=BLK,
                                       block_k=BLK, interpret=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(m + jnp.log(l)),
                               rtol=1e-6, atol=1e-6)
    want = (o_un / jnp.moveaxis(l, 1, -1)[..., None]).astype(q.dtype)
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want, np.float32))


def _census_by_hand(s_q, s_k, bq, bk, qoff, koff):
    """Grid steps whose ``should_run`` is true, and the visible pairs,
    counted pair by pair."""
    rows = qoff + np.arange(s_q)[:, None]
    cols = koff + np.arange(s_k)[None, :]
    visible = rows >= cols
    run = sum(bool(visible[i:i + bq, j:j + bk].any())
              for i in range(0, s_q, bq) for j in range(0, s_k, bk))
    whole = sum(bool(visible[i:i + bq, j:j + bk].all())
                for i in range(0, s_q, bq) for j in range(0, s_k, bk))
    return run, whole, int(visible.sum())


@pytest.mark.parametrize("s,bq,bk,tile,qoff,koff,want", [
    # the parent's blocks on the LM cell's sequence: 6 of 8 blocks, 1.50
    (2048, 512, 1024, None, 0, 0, (2, 4, 2, 1.4993)),
    (2048, 512, 512, None, 0, 0, (6, 4, 6, 1.2494)),
    (2048, 256, 512, None, 0, 0, (12, 8, 12, 1.2494)),
    (2048, 1024, 1024, None, 0, 0, (1, 2, 1, 1.4993)),
    # blocks cut into compute tiles: the tiles decide, not the blocks
    (2048, 1024, 1024, 512, 0, 0, (6, 4, 6, 1.2494)),
    (2048, 2048, 1024, 512, 0, 0, (6, 4, 6, 1.2494)),
    (2048, 2048, 1024, 1024, 0, 0, (1, 2, 1, 1.4993)),
    (2048, 1024, 1024, 256, 0, 0, (28, 8, 28, 1.1245)),
    (512, 128, 128, None, 0, 0, (6, 4, 6, 1.2476)),
    (512, 256, 512, 128, 0, 0, (6, 4, 6, 1.2476)),
    # a ring shard wholly under the diagonal: no mask anywhere, no waste
    (512, 128, 128, None, 512, 0, (0, 0, 16, 1.0)),
    # wholly above it: nothing runs
    (512, 128, 128, None, 0, 512, (16, 0, 0, 1.0)),
    (512, 128, 128, None, 256, 0, (1, 2, 13, 1.0708)),
])
def test_causal_block_census(s, bq, bk, tile, qoff, koff, want):
    c = fa.causal_block_census(s, s, bq, bk, qoff, koff, tile)
    got = (c["skipped"], c["masked"], c["unmasked"],
           round(c["pairs_computed_over_needed"], 4))
    assert got == want
    tq, tk = min(bq, tile or bq), min(bk, tile or bk)
    run, whole, pairs = _census_by_hand(s, s, tq, tk, qoff, koff)
    assert c["masked"] + c["unmasked"] == run
    assert c["skipped"] + run == (s // tq) * (s // tk)
    # a tile the kernel runs without a mask has every pair visible
    assert c["unmasked"] <= whole
    if pairs:
        assert c["pairs_computed_over_needed"] == pytest.approx(
            run * tq * tk / pairs)


def test_causal_block_census_of_the_default_blocks():
    """The registered default on the LM cell's sequence: the forward's
    tiles and the backward kernels', which follow the diagonal more
    closely; no worse than the parent's 1.50 either."""
    bq, bk = fa.default_blocks()
    fwd = fa.causal_block_census(2048, 2048, tile=fa._FWD_TILE)
    bwd = fa.causal_block_census(2048, 2048, tile=fa._BWD_TILE)
    assert fwd == fa.causal_block_census(2048, 2048, bq, bk, 0, 0,
                                         fa._FWD_TILE)
    assert fwd["pairs_computed_over_needed"] <= 1.50
    assert bwd["pairs_computed_over_needed"] <= 1.25
    assert bwd["unmasked"] >= bwd["masked"]      # most tiles build no mask


@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("causal", [False, True])
def test_blocks_cut_into_compute_tiles(tile, causal):
    """A block larger than the kernels' compute tile is cut into squares,
    each of which is skipped, masked or unmasked on its own: same results
    as whole blocks, forward (both finalisations) and backward."""
    q, k, v, do = (x.astype(jnp.float32)
                   for x in rand_bf16(26, 1, S_MIXED, S_MIXED, 2, 64))
    rng = np.random.default_rng(27)
    lse = jnp.asarray(rng.standard_normal((1, 2, S_MIXED)) + 4.0, jnp.float32)
    dD = jnp.asarray(rng.standard_normal((1, 2, S_MIXED)), jnp.float32)
    kw = dict(causal=causal, scale=0.125, block_q=S_MIXED, block_k=256,
              interpret=True)
    for normalize in (True, False):
        for qoff, koff in ((0, 0), (256, 128)):
            whole = fa._attend(q, k, v, qoff, koff, normalize=normalize,
                               tile=None, **kw)
            cut = fa._attend(q, k, v, qoff, koff, normalize=normalize,
                             tile=tile, **kw)
            for a, b in zip(cut, whole):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)
    for qoff, koff in ((0, 0), (256, 128)):
        whole = fa._attend_bwd(q, k, v, do, lse, dD, qoff, koff,
                               grad_dtype=jnp.float32, tile=None, **kw)
        cut = fa._attend_bwd(q, k, v, do, lse, dD, qoff, koff,
                             grad_dtype=jnp.float32, tile=tile, **kw)
        want = sp._bwd_block_jnp(q, k, v, do, lse, dD, qoff, koff, causal,
                                 0.125)
        for a, b, w in zip(cut, whole, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)


def test_short_unaligned_query_block_is_one_block():
    """Sq of whole sublanes but not of whole lanes (cross attention) stays
    eligible as ONE block: its statistics' row is then the array's own
    last dimension."""
    rng = np.random.default_rng(28)
    q, k, v = map(jnp.asarray, rand_qkv(rng, 1, 200, 256, 2, 64))
    assert fa.supports(q, k, v)
    assert fa._resolve_blocks(200, 256, None, None) == (200, 256)
    o, m, l = fa.flash_block_attend(q, k, v, 56, 0, causal=True, scale=0.125,
                                    interpret=True)
    o_ref, m_ref, l_ref = reference(q, k, v, 56, 0, True, 0.125)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-4,
                               atol=1e-4)
