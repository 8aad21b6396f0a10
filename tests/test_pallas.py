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
