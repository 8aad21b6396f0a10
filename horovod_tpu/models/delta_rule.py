"""The gated delta rule, one implementation for the recurrent layers that
run it: Solar Open 2's KDA layer (a log-decay a key channel) and Olmo
Hybrid's gated DeltaNet layer (one log-decay a head), with ``d_k`` key and
query channels and ``d_v`` value channels a head, never taken equal::

    S' = Diag(e^{a_t}) S_{t-1}       a_t <= 0, [H, d_k], or [H, 1]: one a head
    S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T       S[h] in R^{d_k x d_v}
    o_t = S_t^T q^_t

**Decode is the one-step form** (:func:`step`), elementwise in float32: the
stored states are read for both ``S'^T k^`` and ``S'^T q^`` and once more for
the rank-one write. A slot's states lie as ``[H / g, d_k, g * d_v]``
(:class:`Heads`): ``g`` heads side by side on the lanes, the fewest whose
values fill whole 128-lane tiles (:func:`grouped`), so what is resident is
what is stored. KDA's heads of 128 x 128 lie one by one (:data:`HEAD_MAJOR`);
Olmo Hybrid's ``[30, 96, 192]`` would put 192 values on 256 lanes and hold a
third more bytes than it stores, so they lie in pairs, ``[15, 96, 384]``. A
vector a head and key channel meets the states broadcast over the lanes of
its head (a select between the pair's two), never repeated into an array of
the states' size (``[d_k, H * d_v]`` with each number repeated over 192 lanes
is what the compiler writes out in full, three times a layer and step).

**Prefill is the chunked form** (:func:`chunk_scan`), on ONE slot's states
one head by one, ``[H, d_k, d_v]`` (:meth:`Heads.to_heads`): inside a chunk
of ``C`` rows, with ``G`` the running sum of ``a``, the unit
lower-triangular system ``I + strict_tril(b_i k^_i . k^_j e^{G_i - G_j})``
is solved once for ``W`` (right-hand side ``b k^ e^G``) and ``U`` (``b
v``); then ``o = (q^ e^G) S + tril(q^_i . k^_j
e^{G_i - G_j})(U - W S)`` and ``S' = e^{G_C} S + (k^ e^{G_C - G})^T (U - W
S)``. No exponent is positive. The two triangular matrices come from
:func:`per_channel_matrices` (a decay a channel: ``e^{-G}`` alone overflows,
so the differences are taken in sub-blocks of 16 rows) or from
:func:`per_head_matrices` (one decay a head: each matrix is one product on
the matrix unit under a ``[C, C]`` mask of decays). All float32 at
``highest``."""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

SUB_BLOCK = 16                  # rows of a sub-block of a chunk
L2_EPS = 1e-6                   # under the root of q's and k's norms


def unit(x: jax.Array) -> jax.Array:
    """``x / sqrt(|x|^2 + 1e-6)`` over the last axis."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def beta_of(raw: jax.Array) -> jax.Array:
    """``2 sigmoid``: in (0, 2), so a transition's eigenvalue along ``k^``
    may be negative (the configurations' ``allow_neg_eigval``)."""
    return 2.0 * jax.nn.sigmoid(raw)


@dataclasses.dataclass(frozen=True)
class Heads:
    """How a slot's states lie: ``[H / g, d_k, g * d_v]``, ``g`` heads side
    by side on the lanes (``group``; ``d_v`` is only read where ``g`` > 1);
    the states of T slots ``[T, H / g, d_k, g * d_v]``."""
    group: int = 1
    d_v: int = 0

    def keys(self, x: jax.Array) -> jax.Array:
        """A vector a head and key channel, ``[T, H, d_k]`` (or one a head,
        ``[T, H, 1]``), as it meets the states: each head's numbers over its
        own ``d_v`` lanes, by a select between the group's heads."""
        if self.group == 1:
            return x[..., None]
        t, h, n = x.shape
        x = x.reshape(t, h // self.group, self.group, n)
        head = lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, self.group * self.d_v), 3) // self.d_v
        out = x[:, :, 0, :, None]
        for m in range(1, self.group):
            out = jnp.where(head == m, x[:, :, m, :, None], out)
        return out

    def values(self, x: jax.Array) -> jax.Array:
        """A vector a head and value channel, ``[T, H, d_v]``."""
        if self.group == 1:
            return x[:, :, None, :]
        t, h, d = x.shape
        return x.reshape(t, h // self.group, 1, self.group * d)

    def read(self, s: jax.Array, x: jax.Array) -> jax.Array:
        """``S^T x`` a head: ``[T, H, d_v]``."""
        out = jnp.sum(s * self.keys(x), axis=2)
        return out if self.group == 1 else out.reshape(x.shape[0], -1,
                                                       self.d_v)

    def to_heads(self, s: jax.Array) -> jax.Array:
        """One slot's states ``[H / g, d_k, g * d_v]`` as ``[H, d_k,
        d_v]``, what :func:`chunk_scan` takes."""
        p, dk = s.shape[:2]
        return jnp.moveaxis(s.reshape(p, dk, self.group, -1), 2, 1).reshape(
            p * self.group, dk, -1)

    def from_heads(self, s: jax.Array) -> jax.Array:
        h, dk, dv = s.shape
        return jnp.moveaxis(s.reshape(h // self.group, self.group, dk, dv),
                            1, 2).reshape(h // self.group, dk, -1)


HEAD_MAJOR = Heads()


def grouped(heads: int, d_v: int, lanes: int = 128) -> Heads:
    """The fewest heads side by side whose values fill whole tiles of
    ``lanes``: 1 for ``d_v`` 128, 2 for 192."""
    group = next(g for g in range(1, heads + 1)
                 if heads % g == 0 and g * d_v % lanes == 0)
    return Heads(group, d_v)


def step(q, k, v, a, b, s, layout=HEAD_MAJOR):
    """The one-step rule on one row a slot: q, k ``[T, H, d_k]``, v ``[T, H,
    d_v]``, a ``[T, H, d_k]`` or ``[T, H, 1]``, b ``[T, H]``, the states s in
    ``layout``. Returns (o ``[T, H, d_v]``, the new states). float32,
    elementwise: no product's rounding touches the state. ``o = S_t^T q^ =
    S'^T q^ + b (k^ . q^) (v - S'^T k^)``, so both sums read the OLD states."""
    decay = jnp.exp(a)
    from_k = layout.read(s, k * decay)                      # S'^T k^
    from_q = layout.read(s, q * decay)                      # S'^T q^
    u = b[..., None] * (v - from_k)
    o = from_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    s = layout.keys(decay) * s + layout.keys(k) * layout.values(u)
    return o, s


Matrices = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def per_channel_matrices(q, k, g):
    """The two lower-triangular matrices of chunks ``[N, C, ...]``: ``sum_c
    r_ic k_jc exp(G_ic - G_jc)`` for ``j <= i`` with r = k and r = q, ``[2,
    H, N, C, C]``, from q, k and the running log-decay a key channel G ``[N,
    C, H, d_k]``. No exponent is positive: inside a sub-block of
    ``SUB_BLOCK`` rows the differences are taken pair by pair; across
    sub-blocks both factors are taken relative to the later sub-block's
    first row, ``exp(G_i - G_n) exp(G_n - G_j)`` with ``j < n <= i``, and the
    sum over the channels is a product on the matrix unit."""
    hi = lax.Precision.HIGHEST
    n, c, h, d = k.shape
    sub = min(SUB_BLOCK, c)
    nb = c // sub

    def blocks(x):
        return x.reshape(n, nb, sub, h, d)

    gb, kb = blocks(g), blocks(k)
    kq = jnp.stack([kb, blocks(q)])                     # [2, N, nb, sub, ..]
    first = gb[:, :, 0]                                 # [N, nb, H, d]
    rows = kq * jnp.exp(gb - first[:, :, None])
    keys = k[:, None] * jnp.exp(jnp.minimum(
        first[:, :, None] - g[:, None], 0.0))           # [N, nb, C, H, d]
    across = jnp.einsum("xnIbhd,nIjhd->xhnIbj", rows, keys, precision=hi)
    earlier = (jnp.arange(c)[None, :] // sub) < jnp.arange(nb)[:, None]
    across = jnp.where(earlier[:, None, :], across, 0.0).reshape(
        2, h, n, c, c)
    i = jnp.arange(sub)
    seen = (i[:, None] >= i[None, :])[:, :, None, None]     # j <= i
    pair = jnp.where(seen, jnp.exp(jnp.where(
        seen, gb[:, :, :, None] - gb[:, :, None, :], 0.0)), 0.0)
    within = jnp.sum(                                   # [2, N, nb, i, j, H]
        kq[:, :, :, :, None] * pair[None] * kb[None, :, :, None], axis=-1)
    within = jnp.einsum("xnIbjh,IJ->xhnIbJj", within,
                        jnp.eye(nb, dtype=within.dtype))
    return across + within.reshape(2, h, n, c, c)


def per_head_matrices(q, k, g):
    """The same two matrices where the log-decay is one a head, G ``[N, C,
    H, 1]``: ``(r_i . k_j) exp(G_i - G_j)`` for ``j <= i``, each one product
    on the matrix unit times the ``[C, C]`` mask of decays, whose exponents
    are the pairs' differences (never positive), ``[2, H, N, C, C]``."""
    hi = lax.Precision.HIGHEST
    c = k.shape[1]
    gh = jnp.moveaxis(g[..., 0], 2, 0)                  # [H, N, C]
    seen = jnp.tril(jnp.ones((c, c), bool))             # j <= i
    decay = jnp.where(seen, jnp.exp(jnp.where(
        seen, gh[..., :, None] - gh[..., None, :], 0.0)), 0.0)
    products = jnp.einsum("xnihd,njhd->xhnij", jnp.stack([k, q]), k,
                          precision=hi)
    return products * decay[None]


def chunk_scan(q, k, v, a, b, s, chunk: int, matrices: Matrices):
    """The chunked rule over the rows of ONE sequence (q, k ``[R, H, d_k]``,
    v ``[R, H, d_v]``, a ``[R, H, d_k]`` or ``[R, H, 1]``, b ``[R, H]``; R a
    multiple of ``chunk`` or at most it) from the state s ``[H, d_k, d_v]``:
    the recurrence of :func:`step` row after row, computed a chunk at a time,
    with ``matrices`` (:func:`per_channel_matrices` or
    :func:`per_head_matrices`) for the decay's form. What does not depend on
    the state (the triangular system and its solution) is computed for all
    chunks at once; the state is carried from chunk to chunk. Returns (o
    ``[R, H, d_v]``, the state after the last row)."""
    hi = lax.Precision.HIGHEST
    rows, h, d = k.shape
    c = min(chunk, rows)
    if rows % c or c % min(SUB_BLOCK, c):
        raise ValueError(
            f"{rows} rows are no whole number of chunks of {chunk} rows in "
            f"sub-blocks of {SUB_BLOCK}")
    n = rows // c
    q, k, v, a = (x.reshape(n, c, h, x.shape[-1]) for x in (q, k, v, a))
    g = jnp.cumsum(a, axis=1)                           # [N, C, H, .], <= 0
    kk, qk = matrices(q, k, g)                          # [H, N, C, C] each

    def per_head(x):                                    # [N, C, H, ...]
        return jnp.moveaxis(x, 2, 0)                    # [H, N, C, ...]

    bh = per_head(b.reshape(n, c, h))
    system = bh[..., None] * jnp.tril(kk, -1)
    grown = jnp.exp(g)

    rhs = bh[..., None] * jnp.concatenate(
        [per_head(k * grown), per_head(v)], axis=-1)
    wu = lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    to_end = per_head(k * jnp.exp(g[:, -1:] - g))       # k^ e^{G_C - G}
    last = jnp.moveaxis(grown[:, -1], 1, 0)             # [H, N, .]

    def one(s, xs):
        w, u, q_in, qk, to_end, last = xs
        delta = u - jnp.einsum("hck,hkv->hcv", w, s, precision=hi)
        o = jnp.einsum("hck,hkv->hcv", q_in, s, precision=hi) \
            + jnp.einsum("hcj,hjv->hcv", qk, delta, precision=hi)
        s = last[..., None] * s + jnp.einsum(
            "hck,hcv->hkv", to_end, delta, precision=hi)
        return s, o

    by_chunk = jax.tree.map(
        lambda x: jnp.moveaxis(x, 1, 0),
        (wu[..., :d], wu[..., d:], per_head(q * grown), qk, to_end, last))
    s, o = lax.scan(one, s, by_chunk)                   # o [N, H, C, d_v]
    return jnp.moveaxis(o, 1, 2).reshape(rows, h, v.shape[-1]), s
