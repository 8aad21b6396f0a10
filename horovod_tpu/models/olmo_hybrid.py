"""A stack of gated DeltaNet layers (a delta rule with one decay a head) with
one full multi-head attention layer among every four, each followed by a
dense SwiGLU, every sublayer normed AFTER it (Olmo Hybrid's layer) — as the
serving engine runs it.

``h`` the residual stream, ``N`` = RMSNorm (scale only), no norm before a
sublayer::

    h0 = E[token]
    h = h + N(Mixer(h));  h = h + N(SwiGLU(h))    Mixer by ``layer_types``
    logits = N(h) W_head                           the untied head

    GDN(x):  q = silu(conv(x W_q)), k = silu(conv(x W_k))    each [H, d_k]
             v = silu(conv(x W_v))                           [H, d_v]
                                     (depthwise, causal, zeros before t = 0)
             q^ = q / |q| * d_k^(-1/2);  k^ = k / |k|        per head
             g_t = -exp(A_log[h]) * softplus(x W_a + dt_bias)[h]   one a head
             b_t = 2 * sigmoid(x W_b)[h]                     in (0, 2)
             S_t = e^g S_{t-1} + b k^ (v - e^g S_{t-1}^T k^)^T  S[h]: d_k x d_v
             o_t = S_t^T q^
             out = (N_w(o_t) per head * silu(x W_g)) W_o
    MHA(x):  q = N_q(x W_q), k = N_k(x W_k) (over the whole width, before the
             heads split), v = x W_v; no rotary; causal softmax(d^(-1/2) q k)
             in float32, H query heads over as many KV heads; out = attn W_o
    SwiGLU(x) = (silu(x W_gate) * x W_up) W_down

It runs on :mod:`~horovod_tpu.models.granite_hybrid`'s step (the
run-of-like-layers scan, the attention layer and its pages, the slot-state
counters, the convolution's window and tail), which reads the norm's place
from ``post_norm`` and takes the dense SwiGLU where the stack has no experts,
and on :mod:`~horovod_tpu.models.delta_rule`'s rule, which Solar's KDA layer
runs too.

**Two kinds of cache.** An attention layer caches K and V rows in the
engine's pages. A GDN layer caches per SLOT, whatever the request's length,
the state ``[H / 2, d_k, 2 d_v]`` (``delta_rule.grouped``: two heads side by
side on the lanes, so ``[15, 96, 384]`` is whole tiles where ``[30, 96,
192]`` would put 192 values on 256 lanes) and the last ``K - 1`` inputs of
its three convolutions (q, k and v side by side), both float32
(``ServeModel.slot_state``). Padding never moves the state: a row past
``n_real`` (or an idle slot) takes ``g = 0`` and ``b = 0``, so its decay is
1 and it adds nothing.

Decode is the one-step rule on the states as stored; prefill the chunked
rule (chunks of ``gdn_chunk`` rows, the triangular matrices one product each
under a ``[C, C]`` mask of decays) on the slot's state turned to ``[H, d_k,
d_v]`` and back. One chip's share: every leaf replicated. Serving only."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import delta_rule, granite_hybrid as stack
from horovod_tpu.models.delta_rule import beta_of, unit
from horovod_tpu.models.granite_hybrid import (
    ATTENTION, STATE_DTYPE, LayerStack)
from horovod_tpu.models.transformer import _rmsnorm

Params = Dict[str, Any]

GDN = "gdn"
# Olmo-Hybrid-7B's 32 layers: (GDN GDN GDN attention) x 8
PUBLISHED_LAYER_TYPES = tuple(ATTENTION if i % 4 == 3 else GDN
                              for i in range(32))


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(LayerStack):
    """Sizes of the stack (defaults: Olmo-Hybrid-7B as published);
    ``serve_model()`` is what ``ServeEngine`` asks for."""
    vocab_size: int = 100352
    d_model: int = 3840
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    n_heads: int = 30               # attention: query heads
    n_kv_heads: int = 30
    head_dim: int = 128
    d_ff: int = 11008               # the SwiGLU's width
    gdn_n_heads: int = 30           # H: key and value heads alike
    gdn_d_key: int = 96             # d_k
    gdn_d_value: int = 192          # d_v
    gdn_conv: int = 4               # K
    gdn_chunk: int = 64
    norm_eps: float = 1e-6
    max_seq: int = 65536
    dtype: Any = jnp.bfloat16
    tp_axis: Optional[str] = None   # not offered: one chip's share is served
    # what the shared step reads: every sublayer normed after it, no experts,
    # no scalings; constants of the class and no fields
    post_norm = True
    n_routed_experts = 0
    embedding_multiplier = residual_multiplier = logits_scaling = 1.0

    @property
    def attention_multiplier(self) -> float:
        return self.head_dim ** -0.5

    @property
    def key_width(self) -> int:
        """Channels of q and of k: ``H * d_k``."""
        return self.gdn_n_heads * self.gdn_d_key

    @property
    def value_width(self) -> int:
        """Channels of v and of the output gate: ``H * d_v``."""
        return self.gdn_n_heads * self.gdn_d_value

    @property
    def conv_dim(self) -> int:
        """Channels of the three convolutions: q, k and v."""
        return 2 * self.key_width + self.value_width

    @property
    def layout(self) -> delta_rule.Heads:
        """A slot's state ``[H / g, d_k, g d_v]``: heads of 192 values in
        pairs, whole tiles of 128 lanes (``delta_rule.grouped``)."""
        return delta_rule.grouped(self.gdn_n_heads, self.gdn_d_value)

    def serve_model(self):
        """What :class:`horovod_tpu.serving.ServeEngine` asks of this
        model (``serving.model.ServeModel``)."""
        from horovod_tpu.serving.model import ServeModel
        return ServeModel(
            check=_check_serve, cache_rows=stack._cache_rows,
            decode=decode_body, prefill=prefill_body,
            param_specs=param_specs, state=stack._counter_state,
            slot_state=slot_state, stats=serve_stats)


def param_shapes(cfg: OlmoHybridConfig) -> Params:
    """Shape and fan-in of every leaf (``None`` fan-in: not a product's
    weight), in the tree ``init_params`` returns. A layer's ``norm`` is the
    one after its mixer, ``mlp``'s the one after its SwiGLU. The three
    projections of a GDN layer are one matrix ``[q | k | v]``, its three
    convolutions one of that many channels."""
    d, h, cd = cfg.d_model, cfg.gdn_n_heads, cfg.conv_dim
    lg, la, l = cfg.count(GDN), cfg.count(ATTENTION), cfg.n_layers
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    vw, f = cfg.value_width, cfg.d_ff
    gdn = {"norm": ((lg, d), None), "w_qkv": ((lg, d, cd), d),
           "conv_w": ((lg, cfg.gdn_conv, cd), None),
           "w_a": ((lg, d, h), d), "w_b": ((lg, d, h), d),
           "A_log": ((lg, h), None), "dt_bias": ((lg, h), None),
           "w_g": ((lg, d, vw), d), "o_norm": ((lg, cfg.gdn_d_value), None),
           "w_o": ((lg, vw, d), vw)}
    attention = {"norm": ((la, d), None), "wq": ((la, d, hq), d),
                 "wk": ((la, d, hkv), d), "wv": ((la, d, hkv), d),
                 "q_norm": ((la, hq), None), "k_norm": ((la, hkv), None),
                 "wo": ((la, hq, d), hq)}
    mlp = {"norm": ((l, d), None), "w_gate": ((l, d, f), d),
           "w_up": ((l, d, f), d), "w_down": ((l, f, d), f)}
    return {"embed": ((cfg.vocab_size, d), d),
            "head": ((cfg.vocab_size, d), d), "final_norm": ((d,), None),
            "layers": {GDN: gdn, ATTENTION: attention, "mlp": mlp}}


def init_params(cfg: OlmoHybridConfig, rng: jax.Array, dtype: Any = None
                ) -> Params:
    """``granite_hybrid.init_tree`` of this model's leaves: the decay as the
    public gated DeltaNet / Mamba-2 code initialises it (``A`` uniform in
    [1, 16] a head, the step log-uniform in [1e-3, 1e-1] through the inverse
    softplus), the convolutions N(0, 1 / K), every norm scale 1."""
    return stack.init_tree(param_shapes(cfg), rng, dtype or cfg.dtype,
                           cfg.gdn_conv)


def param_specs(cfg: OlmoHybridConfig) -> Params:
    """Every leaf replicated: this module serves one chip's share."""
    return jax.tree.map(lambda sf: P(*([None] * len(sf[0]))),
                        param_shapes(cfg), is_leaf=stack._is_shape)


# ---------------------------------------------------------------------------
# the gated DeltaNet mixer
# ---------------------------------------------------------------------------

def gdn_project(cfg: OlmoHybridConfig, mp: Params, u: jax.Array):
    """Of rows u ``[N, D]``: the convolutions' inputs ``[N, 2 H d_k + H
    d_v]`` (q, k and v side by side), the decay's and beta's inputs ``[N,
    H]`` before their nonlinearities, all float32, and the output gate ``[N,
    H d_v]`` before its silu."""
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("hvd_gdn_proj"):
        qkv = (u @ mp["w_qkv"].astype(dt)).astype(f32)
        a = jnp.dot(u, mp["w_a"].astype(dt), preferred_element_type=f32)
        b = jnp.dot(u, mp["w_b"].astype(dt), preferred_element_type=f32)
        gate = u @ mp["w_g"].astype(dt)
    return qkv, a, b, gate


def gdn_inputs(cfg: OlmoHybridConfig, mp: Params, qkv: jax.Array,
               a: jax.Array, b: jax.Array, live: jax.Array):
    """From the convolved rows qkv and the raw gates: q^ (unit length times
    ``d_k^(-1/2)``) and k^ (unit length) ``[N, H, d_k]``, v ``[N, H, d_v]``,
    the log-decay ``[N, H, 1]`` <= 0 (one a head) and beta ``[N, H]``, the
    last two at 0 on the rows outside ``live`` (such a row decays nothing and
    adds nothing)."""
    h, dk, kw = cfg.gdn_n_heads, cfg.gdn_d_key, cfg.key_width
    q = qkv[:, :kw].reshape(-1, h, dk)
    k = qkv[:, kw:2 * kw].reshape(-1, h, dk)
    v = qkv[:, 2 * kw:].reshape(-1, h, cfg.gdn_d_value)
    g = -jnp.exp(mp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + mp["dt_bias"].astype(jnp.float32))
    return (unit(q) * dk ** -0.5, unit(k), v, (g * live[:, None])[..., None],
            beta_of(b) * live[:, None])


def _rows_first(x: jax.Array) -> jax.Array:
    """x ``[C, channels]`` pinned row by row in memory: left free, the TPU
    compiler lays a chunk's convolution inputs channel-major (the heads of
    96 then split off for free) and, to store their tails, the slots' whole
    tails ``[Lg, 3, slots, C]`` with the three rows on the lanes: 2.1 GB
    copied in and out at this model's sizes; pinned, the tails are laid
    slot-major as Granite's prefill lays its own, 0.14 GB (compile-only
    for a v5e)."""
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(x, Layout(major_to_minor=(0, 1)))


def gdn_chunk_scan(q, k, v, a, b, s, chunk: int):
    """The shared chunked rule (``delta_rule.chunk_scan``) with one decay a
    head: each triangular matrix one product under a mask of decays."""
    return delta_rule.chunk_scan(q, k, v, a, b, s, chunk,
                                 delta_rule.per_head_matrices)


def gdn_gate_out(cfg: OlmoHybridConfig, mp: Params, o: jax.Array,
                 gate: jax.Array) -> jax.Array:
    """``(N_w(o) per head * silu(gate)) W_o``: o ``[N, H, d_v]`` float32,
    gate ``[N, H d_v]``; float32 out."""
    with jax.named_scope("hvd_gdn_gate"):
        y = _rmsnorm(o, mp["o_norm"], eps=cfg.norm_eps).reshape(gate.shape) \
            * jax.nn.silu(gate.astype(jnp.float32))
    with jax.named_scope("hvd_gdn_proj"):
        return jnp.dot(y.astype(cfg.dtype), mp["w_o"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def gdn_decode(cfg: OlmoHybridConfig, mp: Params, u: jax.Array,
               conv: jax.Array, state: jax.Array, layer: jax.Array,
               live: jax.Array):
    """One token a slot through GDN layer ``layer`` (its index among the GDN
    layers): u ``[S, D]``, the whole slot state conv ``[Lg, K-1, S, C]`` and
    state ``[Lg, S, H / g, d_k, g d_v]``, which come back with that layer's
    part advanced for the slots ``live`` and untouched for the others."""
    with jax.named_scope("hvd_gdn"):
        qkv, a, b, gate = gdn_project(cfg, mp, u)
        with jax.named_scope("hvd_gdn_conv"):
            qkv, conv = stack.conv_decode(mp, conv, layer, qkv, live)
        with jax.named_scope("hvd_gdn_scan"):
            o, s = delta_rule.step(
                *gdn_inputs(cfg, mp, qkv, a, b, live),
                lax.dynamic_index_in_dim(state, layer, 0, keepdims=False),
                layout=cfg.layout)
            state = lax.dynamic_update_index_in_dim(
                state, s.astype(state.dtype), layer, 0)
        return gdn_gate_out(cfg, mp, o, gate), conv, state


def gdn_prefill(cfg: OlmoHybridConfig, mp: Params, u: jax.Array,
                conv: jax.Array, state: jax.Array, layer: jax.Array,
                slot: jax.Array, start: jax.Array, n_real: jax.Array):
    """One prefill chunk of ONE sequence through GDN layer ``layer``: rows u
    ``[C, D]`` (bucket-padded, ``n_real`` of them real), from zeros when
    ``start == 0`` and from slot ``slot``'s stored state and tails
    otherwise; the state after the last REAL row and the tails of the last
    real rows are stored."""
    carried = start > 0
    layout = cfg.layout
    with jax.named_scope("hvd_gdn"):
        qkv, a, b, gate = gdn_project(cfg, mp, u)
        with jax.named_scope("hvd_gdn_conv"):
            qkv, conv = stack.conv_prefill(mp, conv, layer, slot,
                                           _rows_first(qkv), carried, n_real)
        with jax.named_scope("hvd_gdn_scan"):
            at = (layer, slot, 0, 0, 0)
            s = lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])
            o, s = gdn_chunk_scan(
                *gdn_inputs(cfg, mp, qkv, a, b,
                            jnp.arange(u.shape[0]) < n_real),
                layout.to_heads(jnp.where(
                    carried, s.reshape(state.shape[2:]), 0.0)
                    .astype(jnp.float32)),
                cfg.gdn_chunk)
            state = lax.dynamic_update_slice(
                state, layout.from_heads(s)[None, None].astype(state.dtype),
                at)
        return gdn_gate_out(cfg, mp, o, gate), conv, state


# ---------------------------------------------------------------------------
# the serving engine's records (serving.model.ServeModel)
# ---------------------------------------------------------------------------

def _check_serve(cfg: OlmoHybridConfig, draft_mode: str) -> None:
    if cfg.tp_axis or draft_mode != "off":
        raise ValueError(
            "serving supports the gated DeltaNet hybrid model on one chip's "
            f"share with plain decode only; got tp_axis={cfg.tp_axis!r}, "
            f"draft mode {draft_mode!r}. Build it with tp_axis None and "
            "HOROVOD_SERVE_DRAFT=off (a rejected draft would have advanced "
            "the recurrent state, which cannot be rolled back).")
    bad = sorted(set(cfg.layer_types) - {GDN, ATTENTION})
    if bad or not cfg.count(GDN) or not cfg.count(ATTENTION):
        raise ValueError(
            f"layer_types must mix {GDN!r} and {ATTENTION!r} layers, at "
            f"least one of each; got {cfg.layer_types}")
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(
            f"served: query heads in whole groups over the KV heads; got "
            f"{cfg.n_heads} heads over {cfg.n_kv_heads} KV heads")


def slot_state(cfg: OlmoHybridConfig, slots: int):
    """What the GDN layers keep a slot, both float32: the last ``K - 1``
    inputs of the three convolutions ``[Lg, K-1, slots, 2 H d_k + H d_v]``
    (the slots on the sublanes, so the three rows are not padded to eight)
    and the state ``[Lg, slots, H / g, d_k, g d_v]`` (``cfg.layout``)."""
    lg, g = cfg.count(GDN), cfg.layout.group
    return (jax.ShapeDtypeStruct(
                (lg, cfg.gdn_conv - 1, slots, cfg.conv_dim), STATE_DTYPE),
            jax.ShapeDtypeStruct(
                (lg, slots, cfg.gdn_n_heads // g, cfg.gdn_d_key,
                 g * cfg.gdn_d_value), STATE_DTYPE))


def serve_stats(cfg: OlmoHybridConfig, state: Tuple[jax.Array, ...]
                ) -> Dict[str, Any]:
    """The stack's ``engine.stats()["ssm"]`` (no routing counters: the stack
    has no experts) and ``resident_bytes``: what the state and the tails
    take where they live, tiles padded (``hvd_serve_ssm_resident_bytes``),
    equal to ``state_bytes`` in this layout."""
    from horovod_tpu import metrics as M
    out = stack.serve_stats(cfg, state)
    ssm = out["ssm"]
    ssm["resident_bytes"] = stack.resident_bytes(*state[-2:])
    M.gauge("hvd_serve_ssm_resident_bytes",
            "Bytes the per-slot recurrent state and convolution tails take "
            "on the device, tiles padded").set(ssm["resident_bytes"])
    return out


def decode_body(cfg: OlmoHybridConfig, params: Params, *args):
    """The stack's decode step (``granite_hybrid.decode_body``) with the GDN
    layers' one-step rule."""
    return stack.decode_body(cfg, params, *args, recurrent=gdn_decode)


def prefill_body(cfg: OlmoHybridConfig, params: Params, *args):
    """The stack's prefill chunk (``granite_hybrid.prefill_body``) with the
    GDN layers' chunked rule."""
    return stack.prefill_body(cfg, params, *args, recurrent=gdn_prefill)
