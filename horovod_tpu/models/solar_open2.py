"""A stack of gated delta-rule linear-attention layers (Kimi Delta
Attention, KDA) with one gated grouped-query attention layer without
positional embedding among every few, each followed by sigmoid-routed
experts plus a shared expert (Solar Open 2's layer) — as the serving engine
runs it.

``h`` the residual stream, ``N`` = RMSNorm (scale only)::

    h0 = E[token]
    u = N(h);  h = h + Mixer(u)                Mixer = GQA if the layer is in
    v = N(h);  h = h + MoE(v) + Shared(v)      ``gqa_layers`` else KDA
    logits = N(h) W_head                        the slice of the untied head

    KDA(u):  [q | k | v] = silu(conv4(u W_qkv))        each [H, d]; depthwise,
                                                causal, zeros before t = 0
             q^ = q / |q| * d^(-1/2);  k^ = k / |k|     per head
             a_t = -exp(A_log[h]) * softplus((u W_f1) W_f2 + dt_bias)
                                                [H, d] <= 0: a log-decay per
                                                key channel
             b_t = 2 * sigmoid(u W_b)           [H], in (0, 2)
             S' = exp(a_t)[:, None] * S_{t-1}   S[h] in R^{d x d}, S_{-1} = 0
             S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T
             o_t = S_t^T q^_t
             out = (N_w(o_t) per head * sigmoid((u W_g1) W_g2)) W_o
    GQA(u):  q = u Wq [H, d], z = u Wz [H, d], k = u Wk, v = u Wv [KVH, d];
             no rotary; causal softmax(d^(-1/2) q k) in float32, query head i
             reads KV head i // (H / KVH); out = (attn * sigmoid(z)) Wo
    MoE(v):  s = sigmoid(v Wr) in float32; the top-k of s + bias;
             g = scaling * s_chosen / sum(s_chosen); sum_k g_k SwiGLU_{e_k}(v)
    Shared(v): one more SwiGLU, always on, gate 1

**Two kinds of cache**, as in :mod:`~horovod_tpu.models.granite_hybrid`,
whose step this module runs (the run-of-like-layers scan, the attention
layer and its pages, the expert block, the counters, the convolution's
window and tail): a GQA layer caches K and V rows in the engine's pages; a
KDA layer caches per SLOT, whatever the request's length, the state ``S``
``[H, d, d]`` and the last ``K - 1`` inputs of its three convolutions (q, k
and v side by side), both float32 (``ServeModel.slot_state``). Padding never
moves the state: a row past ``n_real`` (or an idle slot) takes ``a = 0`` and
``b = 0``, so its decay is 1 and it adds nothing.

**Decode is the one-step recurrence** (:func:`kda_step`), written so that
the stored states are read once for both ``S'^T k^`` and ``S'^T q^`` and
once more for the rank-one write. **Prefill is the chunked rule**
(:func:`kda_chunk_scan`): inside a chunk of ``kda_chunk`` rows, with ``G``
the running sum of ``a``, the unit lower-triangular system ``I +
strict_tril(b_i (k^_i e^{G_i}) . (k^_j e^{-G_j}))`` is solved once for ``W``
(right-hand side ``b k^ e^G``) and ``U`` (``b v``); then ``o = (q^ e^G) S +
tril((q^ e^G)(k^ e^{-G})^T)(U - W S)`` and ``S' = e^{G_C} S + (k^ e^{G_C -
G})^T (U - W S)``. ``e^{-G}`` alone overflows where the decay is strong, so
the two triangular matrices are built from differences ``G_i - G_j`` in
sub-blocks of 16 rows: pair by pair inside a sub-block, through the
sub-block's first row across sub-blocks. All float32 at ``highest``.

The share of the routed experts held here is ``expert_first`` /
``expert_count`` (:mod:`horovod_tpu.parallel.moe`). Serving only."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import granite_hybrid as stack
from horovod_tpu.models.granite_hybrid import (
    ATTENTION, STATE_DTYPE, LayerStack)
from horovod_tpu.models.transformer import _rmsnorm
from horovod_tpu.parallel import moe as moe_lib

Params = Dict[str, Any]

KDA = "kda"
SUB_BLOCK = 16                  # rows of a sub-block of a chunk
L2_EPS = 1e-6                   # under the root of q's and k's norms


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(LayerStack):
    """Sizes of the stack (defaults: Solar-Open2-250B as published) and the
    share of its routed experts held here; ``serve_model()`` is what
    ``ServeEngine`` asks for."""
    vocab_size: int = 196608
    d_model: int = 4096
    n_layers_total: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    n_heads: int = 64               # attention: query heads
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_n_heads: int = 64           # H
    kda_head_dim: int = 128         # d: keys and values
    kda_conv: int = 4               # K
    kda_chunk: int = 64
    n_routed_experts: int = 320     # the router's outputs, all chips'
    top_k: int = 8
    routed_scaling: float = 1.0
    d_expert: int = 1280            # a routed expert's SwiGLU width
    d_shared: int = 1280            # the shared expert's
    # the share of the routed experts this chip holds
    expert_first: int = 0
    expert_count: Optional[int] = None      # None: all of them
    norm_eps: float = 1e-5
    max_seq: int = 1048576
    dtype: Any = jnp.bfloat16
    tp_axis: Optional[str] = None   # not offered: one chip's share is served
    # the shared step's scalings (granite_hybrid.py): none in this model, so
    # constants of the class and no fields
    embedding_multiplier = residual_multiplier = logits_scaling = 1.0

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(ATTENTION if i in self.gqa_layers else KDA
                     for i in range(self.n_layers_total))

    @property
    def attention_multiplier(self) -> float:
        return self.head_dim ** -0.5

    @property
    def kda_width(self) -> int:
        """Channels of q, of k and of v: ``H * d``."""
        return self.kda_n_heads * self.kda_head_dim

    def route(self, v: jax.Array, ep: Params) -> moe_lib.TopKRouting:
        """A sigmoid an output, the chosen renormalised."""
        return moe_lib.topk_sigmoid_route(
            v, ep["router"], ep["router_bias"], self.top_k,
            self.routed_scaling)

    def serve_model(self):
        """What :class:`horovod_tpu.serving.ServeEngine` asks of this
        model (``serving.model.ServeModel``)."""
        from horovod_tpu.serving.model import ServeModel
        return ServeModel(
            check=_check_serve, cache_rows=stack._cache_rows,
            decode=decode_body, prefill=prefill_body,
            param_specs=param_specs, state=stack._counter_state,
            slot_state=slot_state, stats=stack.serve_stats)


def param_shapes(cfg: SolarOpen2Config) -> Params:
    """Shape and fan-in of every leaf (``None`` fan-in: not a product's
    weight), in the tree ``init_params`` returns. The three projections of
    a KDA layer are one matrix ``[q | k | v]``, its three convolutions one
    of three times the channels."""
    d, h, w = cfg.d_model, cfg.kda_n_heads, cfg.kda_width
    r = cfg.kda_head_dim            # the two low-rank gates' rank
    lk, la, l = cfg.count(KDA), cfg.count(ATTENTION), cfg.n_layers
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    e, fe, fs = cfg.held_experts, cfg.d_expert, cfg.d_shared
    kda = {"norm": ((lk, d), None), "w_qkv": ((lk, d, 3 * w), d),
           "conv_w": ((lk, cfg.kda_conv, 3 * w), None),
           "w_f1": ((lk, d, r), d), "w_f2": ((lk, r, w), r),
           "dt_bias": ((lk, w), None), "A_log": ((lk, h), None),
           "w_b": ((lk, d, h), d),
           "w_g1": ((lk, d, r), d), "w_g2": ((lk, r, w), r),
           "o_norm": ((lk, cfg.kda_head_dim), None),
           "w_o": ((lk, w, d), w)}
    attention = {"norm": ((la, d), None), "wq": ((la, d, hq), d),
                 "wz": ((la, d, hq), d), "wk": ((la, d, hkv), d),
                 "wv": ((la, d, hkv), d), "wo": ((la, hq, d), hq)}
    moe = {"norm": ((l, d), None),
           "router": ((l, d, cfg.n_routed_experts), d),
           "router_bias": ((l, cfg.n_routed_experts), None),
           "w_gate": ((l, e, d, fe), d), "w_up": ((l, e, d, fe), d),
           "w_down": ((l, e, fe, d), fe),
           "shared": {"w_gate": ((l, d, fs), d), "w_up": ((l, d, fs), d),
                      "w_down": ((l, fs, d), fs)}}
    return {"embed": ((cfg.vocab_size, d), d),
            "head": ((cfg.vocab_size, d), d), "final_norm": ((d,), None),
            "layers": {KDA: kda, ATTENTION: attention, "moe": moe}}


def init_params(cfg: SolarOpen2Config, rng: jax.Array, dtype: Any = None
                ) -> Params:
    """``granite_hybrid.init_tree`` of this model's leaves (the decay as the
    public KDA / Mamba-2 code initialises it): the router's selection bias
    0."""
    return stack.init_tree(param_shapes(cfg), rng, dtype or cfg.dtype,
                           cfg.kda_conv, zeros=("router_bias",))


def param_specs(cfg: SolarOpen2Config) -> Params:
    """Every leaf replicated: this module serves one chip's share."""
    return jax.tree.map(lambda sf: P(*([None] * len(sf[0]))),
                        param_shapes(cfg), is_leaf=stack._is_shape)


# ---------------------------------------------------------------------------
# the delta-rule mixer
# ---------------------------------------------------------------------------

def kda_project(cfg: SolarOpen2Config, mp: Params, u: jax.Array):
    """Of rows u ``[N, D]``: the convolutions' inputs ``[N, 3 H d]`` (q, k
    and v side by side), the decay's gate ``[N, H d]`` and beta's ``[N, H]``
    before their nonlinearities, all float32, and the output gate ``[N, H
    d]`` before its sigmoid."""
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("hvd_kda_proj"):
        qkv = (u @ mp["w_qkv"].astype(dt)).astype(f32)
        f = jnp.dot((u @ mp["w_f1"].astype(dt)), mp["w_f2"].astype(dt),
                    preferred_element_type=f32)
        beta = jnp.dot(u, mp["w_b"].astype(dt), preferred_element_type=f32)
        gate = (u @ mp["w_g1"].astype(dt)) @ mp["w_g2"].astype(dt)
    return qkv, f, beta, gate


def beta_of(raw: jax.Array) -> jax.Array:
    """``2 sigmoid``: in (0, 2), so a transition's eigenvalue along ``k^``
    may be negative (``kda_allow_neg_eigval``)."""
    return 2.0 * jax.nn.sigmoid(raw)


def kda_inputs(cfg: SolarOpen2Config, mp: Params, qkv: jax.Array,
               f: jax.Array, beta: jax.Array, live: jax.Array):
    """From the convolved rows qkv ``[N, 3 H d]`` and the raw gates: q^ (unit
    length times ``d^(-1/2)``) and k^ (unit length) ``[N, H, d]``, v ``[N, H,
    d]``, the log-decay a ``[N, H, d]`` <= 0 and beta ``[N, H]``, the last
    two at 0 on the rows outside ``live`` (such a row decays nothing and
    adds nothing)."""
    h, d = cfg.kda_n_heads, cfg.kda_head_dim
    q, k, v = (x.reshape(-1, h, d) for x in jnp.split(qkv, 3, axis=-1))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    rate = jnp.exp(mp["A_log"].astype(jnp.float32))[None, :, None]
    a = -rate * jax.nn.softplus(
        (f + mp["dt_bias"].astype(jnp.float32)).reshape(-1, h, d))
    return (unit(q) * d ** -0.5, unit(k), v, a * live[:, None, None],
            beta_of(beta) * live[:, None])


def kda_step(q, k, v, a, b, s):
    """The one-step recurrence on one row a slot: q, k, v, a ``[T, H, d]``,
    b ``[T, H]``, the states s ``[T, H, d, d]`` (a key channel's row of
    values on the lanes). Returns (o ``[T, H, d]``, the new states).
    float32, elementwise: no product's rounding touches the state. The
    states are read ONCE for both sums with the old state, ``S'^T k^`` and
    ``S'^T q^`` (``o = S_t^T q^ = S'^T q^ + b (k^ . q^) (v - S'^T k^)``),
    and once more for the write."""
    decay = jnp.exp(a)
    from_k = jnp.sum(s * (k * decay)[..., None], axis=2)    # S'^T k^
    from_q = jnp.sum(s * (q * decay)[..., None], axis=2)    # S'^T q^
    u = b[..., None] * (v - from_k)
    o = from_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    s = decay[..., None] * s + k[..., None] * u[:, :, None, :]
    return o, s


def _chunk_matrices(q, k, g):
    """The two lower-triangular matrices of chunks ``[N, C, ...]``: ``sum_c
    r_ic k_jc exp(G_ic - G_jc)`` for ``j <= i`` with r = k and r = q, ``[2,
    H, N, C, C]``, from q, k and the running log-decay G ``[N, C, H, d]``.
    No exponent is positive: inside a sub-block of ``SUB_BLOCK`` rows the
    differences are taken pair by pair; across sub-blocks both factors are
    taken relative to the later sub-block's first row, ``exp(G_i - G_n)
    exp(G_n - G_j)`` with ``j < n <= i``, and the sum over the channels is a
    product on the matrix unit."""
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


def kda_chunk_scan(q, k, v, a, b, s, chunk: int):
    """The chunked delta rule over the rows of ONE sequence (q, k, v, a
    ``[R, H, d]``, b ``[R, H]``; R a multiple of ``chunk`` or at most it)
    from the state s ``[H, d, d]``: the recurrence of :func:`kda_step` row
    after row, computed a chunk at a time. What does not depend on the state
    (the triangular system and its solution) is computed for all chunks at
    once; the state is carried from chunk to chunk. Returns (o ``[R, H,
    d]``, the state after the last row)."""
    hi = lax.Precision.HIGHEST
    rows, h, d = k.shape
    c = min(chunk, rows)
    if rows % c or c % min(SUB_BLOCK, c):
        raise ValueError(
            f"{rows} rows are no whole number of chunks of {chunk} rows in "
            f"sub-blocks of {SUB_BLOCK}")
    n = rows // c
    q, k, v, a = (x.reshape(n, c, h, d) for x in (q, k, v, a))
    g = jnp.cumsum(a, axis=1)                           # [N, C, H, d], <= 0
    kk, qk = _chunk_matrices(q, k, g)                   # [H, N, C, C] each

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
    last = jnp.moveaxis(grown[:, -1], 1, 0)             # [H, N, d]

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
    s, o = lax.scan(one, s, by_chunk)                   # o [N, H, C, d]
    return jnp.moveaxis(o, 1, 2).reshape(rows, h, d), s


def kda_gate_out(cfg: SolarOpen2Config, mp: Params, o: jax.Array,
                 gate: jax.Array) -> jax.Array:
    """``(N_w(o) per head * sigmoid(gate)) W_o``: o ``[N, H, d]`` float32,
    gate ``[N, H d]``; float32 out."""
    with jax.named_scope("hvd_kda_gate"):
        y = _rmsnorm(o, mp["o_norm"], eps=cfg.norm_eps).reshape(gate.shape) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
    with jax.named_scope("hvd_kda_proj"):
        return jnp.dot(y.astype(cfg.dtype), mp["w_o"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def kda_decode(cfg: SolarOpen2Config, mp: Params, u: jax.Array,
               conv: jax.Array, state: jax.Array, layer: jax.Array,
               live: jax.Array):
    """One token a slot through KDA layer ``layer`` (its index among the KDA
    layers): u ``[S, D]``, the whole slot state conv ``[Lk, K-1, S, 3 H d]``
    and state ``[Lk, S, H, d, d]``, which come back with that layer's part
    advanced for the slots ``live`` and untouched for the others."""
    with jax.named_scope("hvd_kda"):
        qkv, f, beta, gate = kda_project(cfg, mp, u)
        with jax.named_scope("hvd_kda_conv"):
            qkv, conv = stack.conv_decode(mp, conv, layer, qkv, live)
        with jax.named_scope("hvd_kda_scan"):
            o, s = kda_step(
                *kda_inputs(cfg, mp, qkv, f, beta, live),
                lax.dynamic_index_in_dim(state, layer, 0, keepdims=False))
            state = lax.dynamic_update_index_in_dim(
                state, s.astype(state.dtype), layer, 0)
        return kda_gate_out(cfg, mp, o, gate), conv, state


def kda_prefill(cfg: SolarOpen2Config, mp: Params, u: jax.Array,
                conv: jax.Array, state: jax.Array, layer: jax.Array,
                slot: jax.Array, start: jax.Array, n_real: jax.Array):
    """One prefill chunk of ONE sequence through KDA layer ``layer``: rows u
    ``[C, D]`` (bucket-padded, ``n_real`` of them real), from zeros when
    ``start == 0`` and from slot ``slot``'s stored state and tails
    otherwise; the state after the last REAL row and the tails of the last
    real rows are stored."""
    carried = start > 0
    with jax.named_scope("hvd_kda"):
        qkv, f, beta, gate = kda_project(cfg, mp, u)
        with jax.named_scope("hvd_kda_conv"):
            qkv, conv = stack.conv_prefill(mp, conv, layer, slot, qkv,
                                           carried, n_real)
        with jax.named_scope("hvd_kda_scan"):
            at = (layer, slot, 0, 0, 0)
            s = lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])
            o, s = kda_chunk_scan(
                *kda_inputs(cfg, mp, qkv, f, beta,
                            jnp.arange(u.shape[0]) < n_real),
                jnp.where(carried, s.reshape(state.shape[2:]), 0.0)
                .astype(jnp.float32), cfg.kda_chunk)
            state = lax.dynamic_update_slice(
                state, s[None, None].astype(state.dtype), at)
        return kda_gate_out(cfg, mp, o, gate), conv, state


# ---------------------------------------------------------------------------
# the serving engine's records (serving.model.ServeModel)
# ---------------------------------------------------------------------------

def _check_serve(cfg: SolarOpen2Config, draft_mode: str) -> None:
    if cfg.tp_axis or draft_mode != "off":
        raise ValueError(
            "serving supports the delta-rule hybrid model on one chip's "
            f"share with plain decode only; got tp_axis={cfg.tp_axis!r}, "
            f"draft mode {draft_mode!r}. Build it with tp_axis None and "
            "HOROVOD_SERVE_DRAFT=off (a rejected draft would have advanced "
            "the recurrent state, which cannot be rolled back).")
    if not cfg.count(KDA) or not cfg.count(ATTENTION):
        raise ValueError(
            f"gqa_layers must leave at least one layer of each kind among "
            f"the {cfg.n_layers_total}; got {cfg.gqa_layers}")
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(
            f"served: query heads in whole groups over the KV heads; got "
            f"{cfg.n_heads} heads over {cfg.n_kv_heads} KV heads")
    first, count = cfg.expert_first, cfg.held_experts
    if not (0 <= first and first + count <= cfg.n_routed_experts
            and count >= 1):
        raise ValueError(
            f"the share of experts [{first}, {first + count}) does not lie "
            f"in the {cfg.n_routed_experts} routed experts")


def slot_state(cfg: SolarOpen2Config, slots: int):
    """What the KDA layers keep a slot, both float32: the last ``K - 1``
    inputs of the three convolutions ``[Lk, K-1, slots, 3 H d]`` (the slots
    on the sublanes, so the three rows are not padded to eight) and the
    state ``[Lk, slots, H, d, d]``."""
    lk, d = cfg.count(KDA), cfg.kda_head_dim
    return (jax.ShapeDtypeStruct(
                (lk, cfg.kda_conv - 1, slots, 3 * cfg.kda_width),
                STATE_DTYPE),
            jax.ShapeDtypeStruct(
                (lk, slots, cfg.kda_n_heads, d, d), STATE_DTYPE))


def decode_body(cfg: SolarOpen2Config, params: Params, *args):
    """The stack's decode step (``granite_hybrid.decode_body``) with the KDA
    layers' one-step recurrence."""
    return stack.decode_body(cfg, params, *args, recurrent=kda_decode)


def prefill_body(cfg: SolarOpen2Config, params: Params, *args):
    """The stack's prefill chunk (``granite_hybrid.prefill_body``) with the
    KDA layers' chunked rule."""
    return stack.prefill_body(cfg, params, *args, recurrent=kda_prefill)
