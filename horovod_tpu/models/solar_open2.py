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

**The delta rule is** :mod:`~horovod_tpu.models.delta_rule`'s, the one the
gated DeltaNet layer of ``olmo_hybrid`` runs too: decode its one-step form
(``kda_step``) on the states as stored, ``[H, d, d]`` a slot; prefill its
chunked form (:func:`kda_chunk_scan`, chunks of ``kda_chunk`` rows) with the
triangular matrices of a decay a key channel, built from differences ``G_i
- G_j`` in sub-blocks of 16 rows (``e^{-G}`` alone overflows where the decay
is strong). All float32 at ``highest``.

The share of the routed experts held here is ``expert_first`` /
``expert_count`` (:mod:`horovod_tpu.parallel.moe`). Serving only."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import delta_rule, granite_hybrid as stack
from horovod_tpu.models.delta_rule import beta_of, step as kda_step, unit
from horovod_tpu.models.granite_hybrid import (
    ATTENTION, STATE_DTYPE, LayerStack)
from horovod_tpu.models.transformer import _rmsnorm
from horovod_tpu.parallel import moe as moe_lib

Params = Dict[str, Any]

KDA = "kda"


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


def kda_inputs(cfg: SolarOpen2Config, mp: Params, qkv: jax.Array,
               f: jax.Array, beta: jax.Array, live: jax.Array):
    """From the convolved rows qkv ``[N, 3 H d]`` and the raw gates: q^ (unit
    length times ``d^(-1/2)``) and k^ (unit length) ``[N, H, d]``, v ``[N, H,
    d]``, the log-decay a ``[N, H, d]`` <= 0 and beta ``[N, H]``, the last
    two at 0 on the rows outside ``live`` (such a row decays nothing and
    adds nothing)."""
    h, d = cfg.kda_n_heads, cfg.kda_head_dim
    q, k, v = (x.reshape(-1, h, d) for x in jnp.split(qkv, 3, axis=-1))
    rate = jnp.exp(mp["A_log"].astype(jnp.float32))[None, :, None]
    a = -rate * jax.nn.softplus(
        (f + mp["dt_bias"].astype(jnp.float32)).reshape(-1, h, d))
    return (unit(q) * d ** -0.5, unit(k), v, a * live[:, None, None],
            beta_of(beta) * live[:, None])


def kda_chunk_scan(q, k, v, a, b, s, chunk: int):
    """The shared chunked rule (``delta_rule.chunk_scan``) with a decay a
    key channel: the triangular matrices from sub-blocks."""
    return delta_rule.chunk_scan(q, k, v, a, b, s, chunk,
                                 delta_rule.per_channel_matrices)


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
