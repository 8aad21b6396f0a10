"""A stack whose layers differ in kind — Mamba-2 state-space layers with one
grouped-query attention layer without positional embedding among every few,
each followed by routed experts plus a shared expert (Granite 4.0-H's
layer) — as the serving engine runs it.

``h`` the residual stream, ``N`` = RMSNorm (scale only), ``r`` the residual
multiplier::

    h0 = embedding_multiplier * E[token]
    u = N(h);  h = h + r * Mixer(u)            Mixer by ``layer_types``
    v = N(h);  h = h + r * (MoE(v) + Shared(v))
    logits = N(h) E^T / logits_scaling          E: the tied embedding

    Mamba2(u):  [z | xBC | dt] = u W_in         d_inner | d_inner + 2 N | H
                xBC_t = silu(b + sum_j w[j] * xBC_{t-K+1+j})   causal, depthwise
                [x | B | C] = xBC;  x as [H, P]
                D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
                S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t
                y_t[h] = S_t[h] C_t + D[h] x_t[h]
                out = N_w(y * silu(z)) W_out
    Attention(u): q = u Wq [H, d], k = u Wk, v = u Wv [KVH, d]; no rotary;
                causal softmax(attention_multiplier * q k) in float32, query
                head i reads KV head i // (H / KVH); out = concat Wo
    MoE(v):     l = v Wr in float32; the top-k of l, g = softmax over the
                chosen; sum_k g_k SwiGLU_{e_k}(v)
    Shared(v):  one more SwiGLU, always on, gate 1

**Two kinds of cache.** An attention layer caches K and V rows in the
engine's pages (``kv_cache.dense_rows`` over the attention layers alone). A
Mamba layer caches one fixed-size state per SLOT, whatever the request's
length: the state ``S`` ``[N, H * P]`` and the last ``K - 1`` inputs of the
convolution, both float32 (``ServeModel.slot_state``). A prefill chunk is
told its slot, starts from zeros when it opens a prompt (``start == 0``) and
from what the chunk before stored otherwise; a decode step advances the
rows with ``lengths > 0`` and leaves every other slot's state as it was.
Padding never moves the state: a row past ``n_real`` (or an idle slot)
takes ``D_t = 0``, so its decay is 1 and it adds nothing, and the stored
convolution tail is that of the last real rows.

**Prefill is a chunked scan** (``ssm_chunk_scan``): inside a chunk of
``mamba_chunk_size`` rows the quadratic form (the decay matrix from the
cumulative sum of ``D A``, lower-triangular, products on the MXU), across
chunks the carried state; decode is the one-step recurrence
(``ssm_step``). Both in float32.

The share of the routed experts held here is ``expert_first`` /
``expert_count`` (:mod:`horovod_tpu.parallel.moe`): the router keeps its
width and its experts per token, the absent experts' terms are left out,
and the shared expert is computed where the token lives. The layers of one
kind are stacked (``layers["mamba"]``, ``layers["attention"]``; the expert
block of every layer under ``layers["moe"]``) and a step scans each run of
like layers. Serving only."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.transformer import _rmsnorm, swiglu, visible_softmax
from horovod_tpu.parallel import moe as moe_lib

Params = Dict[str, Any]

MAMBA, ATTENTION = "mamba", "attention"
# Granite 4.0-H Small's 40 layers: attention at 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40))
STATE_DTYPE = jnp.float32       # the recurrent state and the conv tail


class LayerStack:
    """What a config of a stack whose layers differ in kind answers, from
    its ``layer_types`` and its share of the routed experts: what
    :func:`_serve_step`, :func:`experts` and the engine's records ask of it
    (Granite's here; ``models/solar_open2.py``'s and
    ``models/olmo_hybrid.py``'s too).

    ``post_norm``, a constant of the class: where a sublayer's norm stands
    (:func:`residual`), before it (``h + r f(N(h))``, Granite's and Solar's)
    or after it (``h + r N(f(h))``, Olmo 2's order)."""

    post_norm = False

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def has_experts(self) -> bool:
        """Routed experts and a shared one after every mixer
        (``layers["moe"]``, with routing counters); else one dense SwiGLU
        (``layers["mlp"]``) and no routing counters."""
        return self.n_routed_experts > 0

    @property
    def held_experts(self) -> int:
        return (self.n_routed_experts if self.expert_count is None
                else self.expert_count)

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    def runs(self) -> List[Tuple[str, int, int, int]]:
        """The stack as runs of like layers: (kind, the run's first layer,
        that layer's index among its kind, how many)."""
        out: List[Tuple[str, int, int, int]] = []
        seen: Dict[str, int] = {}
        for i, kind in enumerate(self.layer_types):
            if out and out[-1][0] == kind:
                out[-1] = (*out[-1][:3], out[-1][3] + 1)
            else:
                out.append((kind, i, seen.get(kind, 0), 1))
            seen[kind] = seen.get(kind, 0) + 1
        return out


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(LayerStack):
    """Sizes of the hybrid stack (defaults: granite-4.0-h-small as
    published) and the share of its routed experts held here;
    ``serve_model()`` is what ``ServeEngine`` asks for."""
    vocab_size: int = 100352
    d_model: int = 4096
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    n_heads: int = 32               # attention: query heads
    n_kv_heads: int = 8
    attention_multiplier: float = 0.0078125
    mamba_n_heads: int = 128        # H
    mamba_d_head: int = 64          # P; d_inner = H * P
    mamba_d_state: int = 128        # N
    mamba_n_groups: int = 1         # groups of B and C (one is served)
    mamba_d_conv: int = 4           # K
    mamba_chunk_size: int = 256
    n_routed_experts: int = 72      # the router's outputs, all chips'
    top_k: int = 10
    d_expert: int = 768             # a routed expert's SwiGLU width
    d_shared: int = 1536            # the shared expert's
    # the share of the routed experts this chip holds
    expert_first: int = 0
    expert_count: Optional[int] = None      # None: all of them
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16
    tp_axis: Optional[str] = None   # not offered: one chip's share is served

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def route(self, v: jax.Array, ep: Params) -> moe_lib.TopKRouting:
        """Top-k of the logits, softmax over the chosen."""
        return moe_lib.topk_softmax_route(v, ep["router"], self.top_k)

    def serve_model(self):
        """What :class:`horovod_tpu.serving.ServeEngine` asks of this
        model (``serving.model.ServeModel``)."""
        from horovod_tpu.serving.model import ServeModel
        return ServeModel(
            check=_check_serve, cache_rows=_cache_rows, decode=decode_body,
            prefill=prefill_body, param_specs=param_specs,
            state=_counter_state, slot_state=slot_state, stats=serve_stats)


def param_shapes(cfg: GraniteHybridConfig) -> Params:
    """Shape and fan-in of every leaf (``None`` fan-in: not a product's
    weight), in the tree ``init_params`` returns."""
    d, h = cfg.d_model, cfg.mamba_n_heads
    lm, la, l = cfg.count(MAMBA), cfg.count(ATTENTION), cfg.n_layers
    di, cd, k = cfg.d_inner, cfg.conv_dim, cfg.mamba_d_conv
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    e, fe, fs = cfg.held_experts, cfg.d_expert, cfg.d_shared
    mamba = {"norm": ((lm, d), None), "w_in": ((lm, d, di + cd + h), d),
             "conv_w": ((lm, k, cd), None), "conv_b": ((lm, cd), None),
             "dt_bias": ((lm, h), None), "A_log": ((lm, h), None),
             "D": ((lm, h), None), "gate_norm": ((lm, di), None),
             "w_out": ((lm, di, d), di)}
    attention = {"norm": ((la, d), None), "wq": ((la, d, hq), d),
                 "wk": ((la, d, hkv), d), "wv": ((la, d, hkv), d),
                 "wo": ((la, hq, d), hq)}
    moe = {"norm": ((l, d), None),
           "router": ((l, d, cfg.n_routed_experts), d),
           "w_gate": ((l, e, d, fe), d), "w_up": ((l, e, d, fe), d),
           "w_down": ((l, e, fe, d), fe),
           "shared": {"w_gate": ((l, d, fs), d), "w_up": ((l, d, fs), d),
                      "w_down": ((l, fs, d), fs)}}
    return {"embed": ((cfg.vocab_size, d), d), "final_norm": ((d,), None),
            "layers": {MAMBA: mamba, ATTENTION: attention, "moe": moe}}


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_tree(shapes: Params, rng: jax.Array, dtype: Any, conv_taps: int,
              zeros: Tuple[str, ...] = ()) -> Params:
    """A tree of ``(shape, fan-in)`` leaves drawn: products ~ N(0,
    1/fan_in) in ``dtype``, the router float32; float32 the rest: the decay
    as Mamba-2 initialises it (``A_log``: ``A`` uniform in [1, 16];
    ``dt_bias``: the step log-uniform in [1e-3, 1e-1] through the inverse
    softplus), a convolution N(0, 1/K), the leaves named in ``zeros`` 0,
    every other (norm scales, a skip) 1."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def leaf(key, path, shape, fan_in):
        name = jax.tree_util.keystr(path)
        if fan_in is not None:
            w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
            return w if name.endswith("['router']") else w.astype(dtype)
        if name.endswith("['A_log']"):
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name.endswith("['dt_bias']"):
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name.endswith("['conv_w']"):
            return jax.random.normal(key, shape, jnp.float32) \
                * conv_taps ** -0.5
        if name.endswith(tuple(f"['{z}']" for z in zeros)):
            return jnp.zeros(shape, jnp.float32)
        return jnp.ones(shape, jnp.float32)

    return jax.tree.unflatten(treedef, [
        leaf(k, path, *shape_fan_in) for k, (path, shape_fan_in)
        in zip(jax.random.split(rng, len(flat)), flat)])


def init_params(cfg: GraniteHybridConfig, rng: jax.Array,
                dtype: Any = None) -> Params:
    """:func:`init_tree` of this model's leaves: ``D`` = 1, the
    convolution's bias 0."""
    return init_tree(param_shapes(cfg), rng, dtype or cfg.dtype,
                     cfg.mamba_d_conv, zeros=("conv_b",))


def param_specs(cfg: GraniteHybridConfig) -> Params:
    """Every leaf replicated: this module serves one chip's share."""
    return jax.tree.map(lambda sf: P(*([None] * len(sf[0]))),
                        param_shapes(cfg), is_leaf=_is_shape)


# ---------------------------------------------------------------------------
# the state-space mixer
# ---------------------------------------------------------------------------

def _norm(cfg, x, scale):
    return _rmsnorm(x, scale, eps=cfg.norm_eps)


def ssm_project(cfg: GraniteHybridConfig, mp: Params, u: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``[z | xBC | dt] = u W_in`` on rows u ``[N, D]``: z ``[N, d_inner]``
    in the config's dtype, xBC ``[N, conv_dim]`` and dt ``[N, H]`` float32."""
    di, cd = cfg.d_inner, cfg.conv_dim
    with jax.named_scope("hvd_ssm_proj"):
        zxd = u @ mp["w_in"].astype(cfg.dtype)
    return (zxd[:, :di], zxd[:, di:di + cd].astype(jnp.float32),
            zxd[:, di + cd:].astype(jnp.float32))


def conv_window(mp: Params, window: jax.Array) -> jax.Array:
    """The causal depthwise convolution (``conv_w`` ``[K, C]``, a bias
    ``conv_b`` where the model has one) and its silu on a window of inputs
    ``[K - 1 + R, ..., C]`` (the K - 1 inputs before the rows, then the R
    rows, along the first axis): ``[R, ..., C]``, float32."""
    k = mp["conv_w"].shape[0]
    rows = window.shape[0] - k + 1
    acc = mp["conv_b"].astype(jnp.float32) if "conv_b" in mp else 0.0
    for j in range(k):
        acc = acc + mp["conv_w"][j] * window[j:j + rows]
    return jax.nn.silu(acc)


def conv_tail(window: jax.Array, n_real: jax.Array, k1: int) -> jax.Array:
    """The convolution's inputs of the last ``k1`` REAL rows of a chunk:
    rows ``n_real ..`` of its window ``[k1 + C, channels]``, whose first
    ``k1`` rows are the tail before the chunk (so a chunk of fewer real rows
    than ``k1`` keeps what is left of that). Never the bucket's padding."""
    return lax.dynamic_slice_in_dim(window, n_real, k1, axis=0)


def conv_decode(mp: Params, conv: jax.Array, layer: jax.Array, x: jax.Array,
                live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One row a slot through a recurrent layer's convolution: x ``[S, C]``
    behind the tails conv ``[L, K-1, S, C]`` of layer ``layer``. Returns
    (the convolved rows ``[S, C]``, conv with that layer's tails moved on
    for the slots ``live`` and untouched for the others)."""
    tail = lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
    window = jnp.concatenate([tail, x[None]], axis=0)       # [K, S, C]
    conv = lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[None, :, None], window[1:], tail)
        .astype(conv.dtype), layer, 0)
    return conv_window(mp, window)[0], conv


def conv_prefill(mp: Params, conv: jax.Array, layer: jax.Array,
                 slot: jax.Array, x: jax.Array, carried: jax.Array,
                 n_real: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A chunk of ONE sequence through a recurrent layer's convolution: x
    ``[C, channels]`` behind slot ``slot``'s stored tail of layer ``layer``
    (``carried``) or behind zeros. Returns (the convolved rows, conv with
    the tail of the last REAL rows stored)."""
    k1 = conv.shape[1]
    at = (layer, 0, slot, 0)
    tail = lax.dynamic_slice(conv, at, (1, k1, 1, conv.shape[-1]))
    tail = jnp.where(carried, tail.reshape(k1, -1), 0.0)
    window = jnp.concatenate([tail, x], axis=0)             # [K-1 + C, ch]
    conv = lax.dynamic_update_slice(
        conv, conv_tail(window, n_real, k1).reshape(1, k1, 1, -1)
        .astype(conv.dtype), at)
    return conv_window(mp, window), conv


def ssm_inputs(cfg: GraniteHybridConfig, mp: Params, xbc: jax.Array,
               dt: jax.Array, live: jax.Array):
    """From the convolved rows xbc ``[N, conv_dim]`` and the raw steps dt
    ``[N, H]``: x ``[N, H, P]``, B and C ``[N, d_state]``, the steps ``[N,
    H]`` after softplus with the rows outside ``live`` at 0 (such a row
    decays nothing and adds nothing), and ``A`` ``[H]``."""
    di, n = cfg.d_inner, cfg.mamba_d_state
    x = xbc[:, :di].reshape(-1, cfg.mamba_n_heads, cfg.mamba_d_head)
    step = jax.nn.softplus(dt + mp["dt_bias"]) * live[:, None]
    return (x, xbc[:, di:di + n], xbc[:, di + n:], step,
            -jnp.exp(mp["A_log"].astype(jnp.float32)))


def ssm_step(x, step, a, b, c, d_skip, s):
    """The one-step recurrence on one row a slot: x ``[T, H, P]``, step
    ``[T, H]``, a ``[H]``, b and c ``[T, N]``, the states s ``[T, N, H *
    P]`` (the state's ``N`` axis before a head's rows, so the sum over
    ``N`` adds whole rows of lanes). Returns (y ``[T, H, P]``, the new
    states). float32. ``y = S_t C`` is read off the OLD state, ``decay
    (S_{t-1} C) + step x (B C)``: the same sum, from the states as they
    are read for the update."""
    p = x.shape[-1]
    decay = jnp.repeat(jnp.exp(step * a), p, axis=-1)           # [T, H*P]
    dx = (step[:, :, None] * x).reshape(decay.shape)
    y = decay * jnp.sum(s * c[:, :, None], axis=1) \
        + dx * jnp.sum(b * c, axis=-1)[:, None]
    s = decay[:, None, :] * s + b[:, :, None] * dx[:, None, :]
    return y.reshape(x.shape) + d_skip[None, :, None] * x, s


def _ssm_chunk(x, step, a, b, c, s):
    """One chunk of the scan in its quadratic form: rows x ``[Q, H, P]`` of
    ONE sequence, steps ``[Q, H]``, b and c ``[Q, N]``, from the state s
    ``[N, H * P]``. Returns (y without the skip term, the state after the
    chunk). The state stays two-dimensional, so every product with it is a
    plain matrix product and nothing tempts the compiler to hold the
    stored states in another layout than the one they are kept in."""
    hi = lax.Precision.HIGHEST
    q, h, p = x.shape
    cum = jnp.cumsum(step * a, axis=0)                       # [Q, H], <= 0
    below = jnp.tril(jnp.ones((q, q), bool))                 # s <= t
    diff = cum.T[:, :, None] - cum.T[:, None, :]             # [H, t, s]
    decay = jnp.where(below, jnp.exp(jnp.where(below, diff, 0.0)), 0.0)
    cb = jnp.einsum("tn,sn->ts", c, b, precision=hi)
    m = decay * cb[None] * step.T[:, None, :]                # [H, t, s]
    y = jnp.einsum("hts,shp->thp", m, x, precision=hi)
    y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
        "tn,nr->tr", c, s, precision=hi).reshape(q, h, p)
    to_end = jnp.exp(cum[-1][None, :] - cum) * step          # [Q, H]
    s = jnp.repeat(jnp.exp(cum[-1]), p)[None, :] * s + jnp.einsum(
        "sn,sr->nr", b, (to_end[:, :, None] * x).reshape(q, h * p),
        precision=hi)
    return y, s


def ssm_chunk_scan(x, step, a, b, c, d_skip, s, chunk: int):
    """The chunked scan over the rows of ONE sequence (x ``[R, H, P]``, R a
    multiple of ``chunk`` or at most it) from the state s ``[N, H * P]``:
    the recurrence of :func:`ssm_step` row after row, computed a chunk at a
    time, the state carried from chunk to chunk. Returns (y ``[R, H, P]``,
    the state after the last row)."""
    rows = x.shape[0]
    if rows <= chunk:
        y, s = _ssm_chunk(x, step, a, b, c, s)
    else:
        if rows % chunk:
            raise ValueError(
                f"{rows} rows are no whole number of chunks of {chunk}")

        def one(s, xs):
            y, s = _ssm_chunk(*xs[:2], a, *xs[2:], s)
            return s, y

        s, y = lax.scan(one, s, jax.tree.map(
            lambda v: v.reshape(rows // chunk, chunk, *v.shape[1:]),
            (x, step, b, c)))
        y = y.reshape(x.shape)
    return y + d_skip[None, :, None] * x, s


def ssm_gate_out(cfg: GraniteHybridConfig, mp: Params, y: jax.Array,
                 z: jax.Array) -> jax.Array:
    """``N_w(y * silu(z)) W_out``: y ``[N, H, P]`` float32, z ``[N,
    d_inner]``; float32 out."""
    with jax.named_scope("hvd_ssm_gate"):
        g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
        g = _norm(cfg, g, mp["gate_norm"]).astype(cfg.dtype)
    with jax.named_scope("hvd_ssm_proj"):
        return jnp.dot(g, mp["w_out"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def mamba_decode(cfg: GraniteHybridConfig, mp: Params, u: jax.Array,
                 conv: jax.Array, ssm: jax.Array, layer: jax.Array,
                 live: jax.Array):
    """One token a slot through Mamba layer ``layer`` (its index among the
    Mamba layers): u ``[S, D]``, the whole slot state conv ``[Lm, K-1, S,
    C]`` and ssm ``[Lm, S, N, H * P]``, which come back with that layer's
    part advanced for the slots ``live`` and untouched for the others."""
    with jax.named_scope("hvd_ssm"):
        z, xbc, dt = ssm_project(cfg, mp, u)
        with jax.named_scope("hvd_ssm_conv"):
            xbc, conv = conv_decode(mp, conv, layer, xbc, live)
        with jax.named_scope("hvd_ssm_scan"):
            x, b, c, step, a = ssm_inputs(cfg, mp, xbc, dt, live)
            y, s = ssm_step(
                x, step, a, b, c, mp["D"],
                lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False))
            ssm = lax.dynamic_update_index_in_dim(
                ssm, s.astype(ssm.dtype), layer, 0)
        return ssm_gate_out(cfg, mp, y, z), conv, ssm


def mamba_prefill(cfg: GraniteHybridConfig, mp: Params, u: jax.Array,
                  conv: jax.Array, ssm: jax.Array, layer: jax.Array,
                  slot: jax.Array, start: jax.Array, n_real: jax.Array):
    """One prefill chunk of ONE sequence through Mamba layer ``layer``: rows
    u ``[C, D]`` (bucket-padded, ``n_real`` of them real), from zeros when
    ``start == 0`` and from slot ``slot``'s stored state otherwise; the
    state after the last REAL row is stored."""
    carried = start > 0
    with jax.named_scope("hvd_ssm"):
        z, xbc, dt = ssm_project(cfg, mp, u)
        with jax.named_scope("hvd_ssm_conv"):
            xbc, conv = conv_prefill(mp, conv, layer, slot, xbc, carried,
                                     n_real)
        with jax.named_scope("hvd_ssm_scan"):
            x, b, c, step, a = ssm_inputs(
                cfg, mp, xbc, dt, jnp.arange(u.shape[0]) < n_real)
            at = (layer, slot, 0, 0)
            s = lax.dynamic_slice(ssm, at, (1, 1) + ssm.shape[2:])
            y, s = ssm_chunk_scan(
                x, step, a, b, c, mp["D"],
                jnp.where(carried, s.reshape(ssm.shape[2:]), 0.0),
                cfg.mamba_chunk_size)
            ssm = lax.dynamic_update_slice(
                ssm, s[None, None].astype(ssm.dtype), at)
        return ssm_gate_out(cfg, mp, y, z), conv, ssm


# ---------------------------------------------------------------------------
# the attention mixer and the expert block
# ---------------------------------------------------------------------------

def _projected(cfg, ap: Params, u: jax.Array, name: str) -> jax.Array:
    """``u W`` in the config's dtype; where the layer holds a norm of that
    projection (``q_norm`` / ``k_norm``), the float32 product normed over
    its whole width first, before the heads split."""
    dt, norm = cfg.dtype, name[1] + "_norm"
    if norm not in ap:
        return u @ ap[name].astype(dt)
    x = jnp.dot(u, ap[name].astype(dt), preferred_element_type=jnp.float32)
    return _norm(cfg, x, ap[norm]).astype(dt)


def attention_project(cfg: LayerStack, ap: Params, u: jax.Array):
    """q ``[N, H, d]``, k and v ``[N, KVH*d]`` (the pool's row: a token's
    KV heads side by side) of rows u ``[N, D]``; no rotary. q and k normed
    where the layer holds ``q_norm`` and ``k_norm``."""
    n = u.shape[0]
    q = _projected(cfg, ap, u, "wq").reshape(n, cfg.n_heads, cfg.head_dim)
    return q, _projected(cfg, ap, u, "wk"), _projected(cfg, ap, u, "wv")


def attend_gathered(cfg: GraniteHybridConfig, q: jax.Array,
                    k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, pos: jax.Array) -> jax.Array:
    """Prefill's grouped-query attention: the chunk's queries q ``[C, H, d]``
    at positions ``pos`` over ONE sequence's pages in block-table order,
    each seeing the cached positions up to its own; ``[C, H, d]``."""
    from horovod_tpu.serving import kv_cache as kvc
    n, kvh = q.shape[0], cfg.n_kv_heads
    by_head = (-1, kvh, cfg.head_dim)                        # [T, KVH, d]
    kg = kvc.gather_pages(k_pages, block_table).reshape(by_head)
    vg = kvc.gather_pages(v_pages, block_table).reshape(by_head)
    qg = q.reshape(n, kvh, cfg.n_heads // kvh, -1)
    s = jnp.einsum("nkgd,tkd->nkgt", qg, kg,
                   preferred_element_type=jnp.float32) \
        * cfg.attention_multiplier
    visible = jnp.arange(kg.shape[0], dtype=jnp.int32)[None, :] \
        <= pos[:, None]
    p = visible_softmax(s.reshape(n, cfg.n_heads, -1), visible)
    o = jnp.einsum("nkgt,tkd->nkgd", p.reshape(s.shape).astype(cfg.dtype),
                   vg)
    return o.reshape(q.shape)


def experts(cfg: LayerStack, ep: Params, h: jax.Array,
            valid: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """The expert half of a layer on the residual stream h ``[N, D]``
    (float32): this chip's routed experts' terms, chosen by the config's
    gate rule (``cfg.route``), plus the shared expert. Returns (h, the
    routing counters of the call over the rows ``valid``). The router reads
    the float32 rows, the products the config's dtype."""
    v = _norm(cfg, h, ep["norm"])
    share = dict(n_routed=cfg.n_routed_experts, first=cfg.expert_first)
    with jax.named_scope("hvd_moe"):
        with jax.named_scope("hvd_moe_router"):
            routing = cfg.route(v, ep)
            counts = moe_lib.share_counts(
                routing, count=cfg.held_experts, valid=valid, **share)
        s = moe_lib.expert_share_ffn(
            v.astype(cfg.dtype), routing, ep["w_gate"], ep["w_up"],
            ep["w_down"], **share)
    with jax.named_scope("hvd_mlp"):
        s = s + swiglu(cfg, ep["shared"], v.astype(cfg.dtype))
    return h + cfg.residual_multiplier * s, counts


def residual(cfg: LayerStack, h: jax.Array, scale: jax.Array, sublayer):
    """One residual sublayer on the stream h ``[N, D]`` (float32) with its
    norm where the stack puts it (``cfg.post_norm``, the one place it is
    read): ``h + r f(N(h))`` before, ``h + r N(f(h))`` after. ``sublayer``
    takes the rows in the config's dtype and returns (its float32 output,
    whatever else it hands on); so does this, with h in the output's place."""
    if cfg.post_norm:
        o, *rest = sublayer(h.astype(cfg.dtype))
        o = _norm(cfg, o, scale)
    else:
        o, *rest = sublayer(_norm(cfg, h, scale).astype(cfg.dtype))
    return (h + cfg.residual_multiplier * o, *rest)


def dense_mlp(cfg: LayerStack, fp: Params, h: jax.Array) -> jax.Array:
    """The dense half of a layer of a stack without experts: the SwiGLU under
    ``hvd_mlp``, its norm outside the scope (as the dense block's)."""
    def mlp(u):
        with jax.named_scope("hvd_mlp"):
            return (swiglu(cfg, fp, u),)
    return residual(cfg, h, fp["norm"], mlp)[0]


def logits_of(cfg: LayerStack, params: Params, h: jax.Array) -> jax.Array:
    """Over the rows of the vocabulary held here: an untied ``head`` where
    the model has one, else the tied embedding."""
    x = _norm(cfg, h, params["final_norm"]).astype(cfg.dtype)
    head = params["head"] if "head" in params else params["embed"]
    return jnp.einsum("...d,vd->...v", x, head.astype(cfg.dtype),
                      preferred_element_type=jnp.float32) \
        / cfg.logits_scaling


# ---------------------------------------------------------------------------
# the serving engine's step bodies (serving.model.ServeModel)
# ---------------------------------------------------------------------------

def _check_serve(cfg: GraniteHybridConfig, draft_mode: str) -> None:
    if cfg.tp_axis or draft_mode != "off":
        raise ValueError(
            "serving supports the hybrid state-space model on one chip's "
            f"share with plain decode only; got tp_axis={cfg.tp_axis!r}, "
            f"draft mode {draft_mode!r}. Build it with tp_axis None and "
            "HOROVOD_SERVE_DRAFT=off (a rejected draft would have advanced "
            "the recurrent state, which cannot be rolled back).")
    bad = sorted(set(cfg.layer_types) - {MAMBA, ATTENTION})
    if bad or not cfg.count(MAMBA) or not cfg.count(ATTENTION):
        raise ValueError(
            f"layer_types must mix {MAMBA!r} and {ATTENTION!r} layers, at "
            f"least one of each; got {cfg.layer_types}")
    if cfg.mamba_n_groups != 1 or cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(
            f"served: one group of B and C and query heads in whole groups "
            f"over the KV heads; got mamba_n_groups={cfg.mamba_n_groups}, "
            f"{cfg.n_heads} heads over {cfg.n_kv_heads} KV heads")
    first, count = cfg.expert_first, cfg.held_experts
    if not (0 <= first and first + count <= cfg.n_routed_experts
            and count >= 1):
        raise ValueError(
            f"the share of experts [{first}, {first + count}) does not lie "
            f"in the {cfg.n_routed_experts} routed experts")


def _cache_rows(cfg: LayerStack):
    from horovod_tpu.serving.kv_cache import dense_rows
    return dense_rows(cfg.count(ATTENTION), cfg.n_kv_heads, cfg.head_dim)


# the state-space counters ``[2, 1, 3]`` (low and high words): prefill chunks
# that opened a prompt, that continued one, decode rows advanced
RESETS, CARRIED, DECODE_ROWS = 0, 1, 2


def _counter_state(cfg: LayerStack):
    """The routing counters (a stack with experts only), then the recurrent
    layers'."""
    recurrent = jax.ShapeDtypeStruct((2, 1, 3), jnp.uint32)
    if not cfg.has_experts:
        return (recurrent,)
    return (moe_lib.share_counter_state(cfg.held_experts), recurrent)


def slot_state(cfg: GraniteHybridConfig, slots: int):
    """What the Mamba layers keep a slot, both float32: the convolution's
    last ``K - 1`` inputs ``[Lm, K-1, slots, conv_dim]`` (the slots on the
    sublanes, so the three rows are not padded to eight) and the state
    ``[Lm, slots, N, H * P]`` (two-dimensional a slot, see
    :func:`_ssm_chunk`; ``N`` first, see :func:`ssm_step`)."""
    lm = cfg.count(MAMBA)
    return (jax.ShapeDtypeStruct(
                (lm, cfg.mamba_d_conv - 1, slots, cfg.conv_dim), STATE_DTYPE),
            jax.ShapeDtypeStruct(
                (lm, slots, cfg.mamba_d_state, cfg.d_inner), STATE_DTYPE))


def serve_stats(cfg: LayerStack, state: Tuple[jax.Array, ...]
                ) -> Dict[str, Any]:
    """``engine.stats()["moe"]`` (a stack with experts) and ``["ssm"]``:
    the counters read back (the one place), published as ``hvd_serve_moe_*``
    / ``hvd_serve_ssm_*`` gauges. ``state``: the routing counters (a stack
    with experts), the recurrent layers' counters, the convolution tails
    ``[L, K-1, slots, C]`` and the recurrent state ``[L, slots, ...]``."""
    from horovod_tpu import metrics as M
    *routing, counted, conv, ssm = state
    totals = [int(v) for v in moe_lib.counter_totals(counted)[0]]
    out = {"state_bytes": int(conv.nbytes) + int(ssm.nbytes),
           "slots": int(ssm.shape[1]), "layers": int(ssm.shape[0]),
           "resets": totals[RESETS], "chunks_carried": totals[CARRIED],
           "decode_rows": totals[DECODE_ROWS]}
    for key, what in (
            ("state_bytes", "Bytes of per-slot recurrent state and "
             "convolution tail resident on the device"),
            ("slots", "Slots that hold a recurrent state"),
            ("layers", "Recurrent layers that keep a state a slot"),
            ("resets", "Prefill chunks that opened a prompt from a zero "
             "state"),
            ("chunks_carried", "Prefill chunks that continued from the "
             "state the chunk before stored"),
            ("decode_rows", "Slot states a decode step advanced")):
        M.gauge(f"hvd_serve_ssm_{key}", what).set(out[key])
    if not routing:
        return {"ssm": out}
    return {**moe_lib.share_routing_stats(routing[0], cfg.expert_first,
                                          cfg.held_experts), "ssm": out}


def resident_bytes(*arrays: jax.Array) -> int:
    """Bytes the arrays take where they live: each one's minor dimensions
    padded to its layout's tile (the TPU's ``(8, 128)`` for float32), its
    own bytes on a device that tiles nothing."""
    total = 0
    for a in arrays:
        layout = a.format.layout
        dims = list(a.shape)
        tile = layout.tiling[0] if layout.tiling else ()
        for axis, t in zip(layout.major_to_minor[-len(tile):] if tile
                           else (), tile):
            dims[axis] = -(-dims[axis] // t) * t
        total += math.prod(dims) * a.dtype.itemsize
    return total


def _serve_step(cfg: LayerStack, params: Params, held: Tuple,
                block_tables: jax.Array, tokens: jax.Array,
                counted: jax.Array, recurrent, attention, program: int,
                ssm_counts: jax.Array, out_row: Optional[jax.Array] = None):
    """What a decode step and a prefill chunk share: embed ``tokens``
    ``[N]``, every run of like layers in a scan of its own — an attention
    layer through ``attention(ap, u, flat pool, block tables, scratch)``, a
    layer of the other kind (Mamba-2 here) through ``recurrent(mp, u, conv,
    ssm, i)``, ``i`` the layer's index among its kind — each a residual
    sublayer (:func:`residual`) followed by the expert block, or the dense
    SwiGLU where the stack has no experts, then the head (of row ``out_row``
    only, if given) and argmax. ``held``: the K and V pools, the routing
    counters (a stack with experts), the state-space counters, the slot
    state."""
    from horovod_tpu.serving import kv_cache as kvc
    k_pages, v_pages, *routing, ssm_counters, conv, ssm = held
    layers = params["layers"]
    h = params["embed"][tokens].astype(jnp.float32) \
        * cfg.embedding_multiplier                              # [N, D]

    def run_of(kind):
        def body(carry, index):
            h, flat, conv, ssm, total = carry
            li, ki = index
            mp = jax.tree.map(lambda a: a[ki], layers[kind])

            def mixer(u):
                if kind == ATTENTION:
                    bt, scratch = kvc.block_pages(k_pages.shape, ki,
                                                  block_tables)
                    o, pool = attention(mp, u, flat, bt, scratch)
                    return o, pool, conv, ssm
                o, state_conv, state = recurrent(mp, u, conv, ssm, ki)
                return o, flat, state_conv, state

            h, flat, conv, ssm = residual(cfg, h, mp["norm"], mixer)
            if not cfg.has_experts:
                h = dense_mlp(cfg, jax.tree.map(lambda a: a[li],
                                                layers["mlp"]), h)
                return (h, flat, conv, ssm, total), None
            h, counts = experts(
                cfg, jax.tree.map(lambda a: a[li], layers["moe"]), h,
                counted)
            return (h, flat, conv, ssm, total + counts), None
        return body

    carry = (h, kvc.flat_pool(k_pages, v_pages), conv, ssm,
             jnp.zeros((routing[0].shape[-1],), jnp.int32) if routing
             else ())
    for kind, first, first_of_kind, n in cfg.runs():
        steps = jnp.arange(n, dtype=jnp.int32)
        carry, _ = lax.scan(run_of(kind), carry,
                            (first + steps, first_of_kind + steps))
    h, flat, conv, ssm, total = carry
    if out_row is not None:
        h = jnp.take(h, out_row, axis=0)                            # [D]
    logits = logits_of(cfg, params, h)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (*(p.reshape(k_pages.shape) for p in flat),
            *(moe_lib.add_share_counts(r, total, program) for r in routing),
            moe_lib.add_share_counts(ssm_counters, ssm_counts, 0),
            conv, ssm, next_tokens, logits)


def _gated(cfg, ap, u, o):
    """What the attention gives, under the layer's output gate where it has
    one (``wz``): ``o * sigmoid(u Wz)``, a gate a channel, one multiply
    before ``Wo``."""
    if "wz" not in ap:
        return o
    z = (u @ ap["wz"].astype(cfg.dtype)).reshape(o.shape)
    return (o.astype(jnp.float32)
            * jax.nn.sigmoid(z.astype(jnp.float32))).astype(o.dtype)


def _attention_out(cfg, ap, o):
    return jnp.dot(o.reshape(o.shape[0], -1).astype(cfg.dtype),
                   ap["wo"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def decode_body(cfg: LayerStack, params: Params, *args, recurrent=None):
    """One decode step over all slots: ``(k_pages, v_pages, routing
    counters (a stack with experts), state-space counters, conv, ssm,
    block_tables, lengths, tokens)``. The slots with ``lengths > 0``
    decode: their K and V rows go to their pages, their recurrent state
    advances by one token. Every other slot (empty, or mid-prefill) writes
    to the scratch page and keeps its state bit for bit. ``recurrent``: the
    other kind of layer's decode (``mamba_decode``'s signature; that by
    default)."""
    from horovod_tpu.serving import kv_cache as kvc
    *held, block_tables, lengths, tokens = args
    live = lengths > 0
    valid = lengths < block_tables.shape[1] * held[0].shape[2]
    recurrent = recurrent or mamba_decode

    def layer(mp, u, conv, ssm, i):
        return recurrent(cfg, mp, u, conv, ssm, i, live)

    def attention(ap, u, flat, bt, scratch):
        q, k, v = attention_project(cfg, ap, u)
        with jax.named_scope("hvd_kv_write"):
            flat = kvc.write_token_rows(flat, (k, v), bt, lengths,
                                        valid=valid, scratch=scratch)
        with jax.named_scope("hvd_attention"):
            o = _gated(cfg, ap, u, kvc.paged_decode_attention(
                q, *flat, bt, lengths + 1, cfg.attention_multiplier))
        return _attention_out(cfg, ap, o), flat

    counts = jnp.zeros((3,), jnp.int32).at[DECODE_ROWS].set(
        jnp.sum(live, dtype=jnp.int32))
    return _serve_step(cfg, params, tuple(held), block_tables, tokens, live,
                       layer, attention, moe_lib.DECODE, counts)


def prefill_body(cfg: LayerStack, params: Params, *args, recurrent=None):
    """One prefill chunk of ONE sequence, told its slot: ``(k_pages,
    v_pages, routing counters (a stack with experts), state-space counters,
    conv, ssm, block_table, slot, start, n_real, tokens)``; tokens ``[C]``
    (bucket-padded) at positions ``start ..``. The attention layers write
    the chunk's K and V rows to the pages and attend over the cached prefix
    and the chunk; the recurrent layers (``recurrent``: ``mamba_prefill``'s
    signature; that by default) go on from the slot's stored state (from
    zeros at ``start == 0``) and store the state after the last real row."""
    from horovod_tpu.serving import kv_cache as kvc
    *held, block_table, slot, start, n_real, tokens = args
    c = tokens.shape[0]
    pos = start + jnp.arange(c, dtype=jnp.int32)
    recurrent = recurrent or mamba_prefill

    def layer(mp, u, conv, ssm, i):
        return recurrent(cfg, mp, u, conv, ssm, i, slot, start, n_real)

    def attention(ap, u, flat, bt, scratch):
        q, k, v = attention_project(cfg, ap, u)
        with jax.named_scope("hvd_kv_write"):
            flat = kvc.write_chunk_pages(flat, (k, v), bt, start, n_real,
                                        scratch=scratch)
        with jax.named_scope("hvd_attention"):
            o = _gated(cfg, ap, u, attend_gathered(cfg, q, *flat, bt, pos))
        return _attention_out(cfg, ap, o), flat

    opened = (start == 0).astype(jnp.int32)
    counts = jnp.zeros((3,), jnp.int32).at[RESETS].set(opened) \
        .at[CARRIED].set(1 - opened)
    return _serve_step(cfg, params, tuple(held), block_table, tokens,
                       jnp.arange(c) < n_real, layer, attention,
                       moe_lib.PREFILL,
                       counts, out_row=jnp.maximum(n_real - 1, 0))
