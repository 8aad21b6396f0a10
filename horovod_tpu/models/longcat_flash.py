"""The shortcut-MoE layer with latent attention (LongCat-Flash's layer), as
the serving engine runs it.

Per layer, ``h`` the residual stream, ``N`` = RMSNorm (scale only), for
``i`` in 0, 1::

    h = h + MLA_i(N(h))
    u = N(h)
    if i == 0:  s = MoE(u)              # the shortcut: from block 0's FFN input
    h = h + SwiGLU_i(u)                 # dense: (silu(u Wg) * (u Wu)) Wd
    if i == 1:  h = h + s

then the final ``N`` and an output head untied from the embedding. The
residual stream ``h`` is float32 whatever the weights' dtype: a product reads
its input in the config's dtype and what it gives is added up in float32;
the router reads the float32 ``u``.

MLA (multi-head latent attention: :mod:`horovod_tpu.models.mla`, whose
blocks and step this module runs): the latents scaled, ``a_q = sqrt(d_model
/ q_lora_rank)`` on the query's and ``a_kv = sqrt(d_model / kv_lora_rank)``
on the normed ``c``; plain rotary at ``rope_theta``; softmax scale ``1 /
sqrt(nope + rope)``. The cache holds, per token and attention block, ``c``
and ``k_r``; a prefill chunk expands keys and values from it, a decode step
absorbs ``Wkvb``.

MoE (:mod:`horovod_tpu.parallel.moe`): softmax router over every routed and
zero-compute (identity) expert, top-k of ``p + bias``, weights ``scaling *
p`` not renormalised. The layer holds routed experts ``[expert_first,
expert_first + expert_count)`` of ``n_routed_experts`` and computes their
terms and the identity terms of its own tokens; the absent experts' terms
are left out and that partial ``s`` goes on (one chip's share of a layer
that several chips hold; summed over the shares, identity terms once, it is
the whole layer).

Parameters (``init_params`` / ``param_specs``): every per-layer leaf is
stacked over the layers, the two attention blocks and the two dense FFNs of
a layer are separate leaves (``mla`` / ``ffn``: a pair of dictionaries), so a
step slices a layer out and nothing else. Serving only: there is no training
step for this layer yet (ROADMAP Reach).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import mla
from horovod_tpu.models.mla import (  # noqa: F401 - read here by tests
    mla_attend_absorbed, mla_attend_expanded)
from horovod_tpu.models.mla import norm as _norm
from horovod_tpu.models.transformer import swiglu
from horovod_tpu.parallel import moe as moe_lib

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LongCatFlashConfig:
    vocab_size: int = 131072
    d_model: int = 6144
    n_heads: int = 64
    n_layers: int = 28
    d_ff: int = 12288               # each of a layer's two dense SwiGLU FFNs
    d_expert: int = 2048            # a routed expert's SwiGLU width
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    mla_scale_q_lora: bool = True   # a_q = sqrt(d_model / q_lora_rank)
    mla_scale_kv_lora: bool = True  # a_kv = sqrt(d_model / kv_lora_rank)
    n_routed_experts: int = 512     # the router's routed outputs, all chips'
    n_zero_experts: int = 256       # zero-compute identity experts
    top_k: int = 12
    routed_scaling: float = 6.0
    # the share of the routed experts this chip holds
    expert_first: int = 0
    expert_count: Optional[int] = None      # None: all of them
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16
    tp_axis: Optional[str] = None   # not offered: one chip's share is served
    # the rotary at rope_theta, unstretched: a constant of the class
    rope_scaling = None

    @property
    def held_experts(self) -> int:
        return (self.n_routed_experts if self.expert_count is None
                else self.expert_count)

    @property
    def attention_blocks(self) -> int:
        """Cached blocks: two attention blocks a layer."""
        return 2 * self.n_layers

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.n_zero_experts

    @property
    def cache_row(self) -> int:
        """Numbers one token caches in one attention block."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def a_q(self) -> float:
        return ((self.d_model / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0)

    @property
    def a_kv(self) -> float:
        return ((self.d_model / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)

    def serve_model(self):
        """What :class:`horovod_tpu.serving.ServeEngine` asks of this
        model (``serving.model.ServeModel``)."""
        from horovod_tpu.serving.model import ServeModel
        return ServeModel(
            check=_check_serve, cache_rows=mla.cache_rows,
            decode=decode_body, prefill=prefill_body, param_specs=param_specs,
            state=_counter_state, stats=routing_stats)


def param_shapes(cfg: LongCatFlashConfig) -> Params:
    """Shape and fan-in of every leaf (``None`` fan-in: a norm scale or the
    routing bias), in the tree ``init_params`` returns."""
    d, l, h = cfg.d_model, cfg.n_layers, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    f, fe, e = cfg.d_ff, cfg.d_expert, cfg.held_experts
    mla = {"attn_norm": ((l, d), None),
           "wq_a": ((l, d, rq), d), "q_norm": ((l, rq), None),
           "wq_b": ((l, rq, h * (dn + dr)), rq),
           "wkv_a": ((l, d, rkv + dr), d), "kv_norm": ((l, rkv), None),
           "wkv_b": ((l, rkv, h * (dn + dv)), rkv),
           "wo": ((l, h * dv, d), h * dv)}
    ffn = {"ffn_norm": ((l, d), None), "w_gate": ((l, d, f), d),
           "w_up": ((l, d, f), d), "w_down": ((l, f, d), f)}
    moe = {"router": ((l, d, cfg.router_width), d),
           "router_bias": ((l, cfg.router_width), None),
           "w_gate": ((l, e, d, fe), d), "w_up": ((l, e, d, fe), d),
           "w_down": ((l, e, fe, d), fe)}
    return {"embed": ((cfg.vocab_size, d), d), "final_norm": ((d,), None),
            "head": ((d, cfg.vocab_size), d),
            "layers": {"mla": (dict(mla), dict(mla)),
                       "ffn": (dict(ffn), dict(ffn)), "moe": moe}}


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(cfg: LongCatFlashConfig, rng: jax.Array,
                dtype: Any = None) -> Params:
    """Products ~ N(0, 1/fan_in) in ``dtype`` (the config's by default), norm
    scales 1, routing bias 0; the router and the bias stay float32."""
    dtype = dtype or cfg.dtype
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)

    def leaf(key, path, shape, fan_in):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            return jnp.zeros(shape, jnp.float32)
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        return w if name.endswith("['router']") else w.astype(dtype)

    return jax.tree.unflatten(treedef, [
        leaf(k, path, *shape_fan_in) for k, (path, shape_fan_in)
        in zip(jax.random.split(rng, len(flat)), flat)])


def param_specs(cfg: LongCatFlashConfig) -> Params:
    """Every leaf replicated: this module serves one chip's share (the
    share itself is ``expert_first`` / ``expert_count``, not a mesh axis)."""
    return jax.tree.map(lambda sf: P(*([None] * len(sf[0]))),
                        param_shapes(cfg), is_leaf=_is_shape)


# ---------------------------------------------------------------------------
# pieces of a layer
# ---------------------------------------------------------------------------

def moe_share(cfg: LongCatFlashConfig, mp: Params, u: jax.Array,
              valid: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """(this chip's part of the expert block's output on rows u ``[N, D]``,
    float32; the routing counters of the call over the rows ``valid``).
    ``u`` arrives in float32: the router reads it as it is (a score rounded
    to bfloat16 flips a choice between near neighbours), the experts'
    products read it in the config's dtype."""
    share = dict(n_routed=cfg.n_routed_experts, first=cfg.expert_first)
    with jax.named_scope("hvd_moe"):
        with jax.named_scope("hvd_moe_router"):
            routing = moe_lib.topk_route(
                u, mp["router"], mp["router_bias"], cfg.top_k,
                cfg.routed_scaling)
            counts = moe_lib.share_counts(
                routing, count=cfg.held_experts, valid=valid, **share)
        s = moe_lib.expert_share_ffn(
            u.astype(cfg.dtype), routing, mp["w_gate"], mp["w_up"],
            mp["w_down"], **share)
    return s, counts


def layer(cfg: LongCatFlashConfig, lp: Params, h: jax.Array, attend,
          valid: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """One layer on the residual stream h ``[N, D]``, which is float32 (the
    products' inputs are cast to the config's dtype, what they give is added
    up in float32). ``attend(i, x)``: attention block i's output ``[N, H *
    v]`` (before ``Wo``) for its normed input x; the caller owns
    projections' positions and the cache. Returns (h, the layer's routing
    counters)."""
    dt = cfg.dtype
    s = counts = None
    for i in (0, 1):
        bp, fp = lp["mla"][i], lp["ffn"][i]
        o = attend(i, _norm(cfg, h, bp["attn_norm"]).astype(dt))
        with jax.named_scope("hvd_mla_proj"):
            h = h + jnp.dot(o, bp["wo"].astype(dt),
                            preferred_element_type=jnp.float32)
        u = _norm(cfg, h, fp["ffn_norm"])
        if i == 0:
            s, counts = moe_share(cfg, lp["moe"], u, valid)
        with jax.named_scope("hvd_mlp"):
            h = h + swiglu(cfg, fp, u.astype(dt))
    return h + s, counts


# ---------------------------------------------------------------------------
# the serving engine's step bodies (serving.model.ServeModel)
# ---------------------------------------------------------------------------

def _check_serve(cfg: LongCatFlashConfig, draft_mode: str) -> None:
    if cfg.tp_axis or draft_mode != "off":
        raise ValueError(
            "serving supports the shortcut-MoE latent-attention model on "
            f"one chip's share with plain decode only; got tp_axis="
            f"{cfg.tp_axis!r}, draft mode {draft_mode!r}. Build it with "
            "tp_axis None and HOROVOD_SERVE_DRAFT=off (a verify step would "
            "count its rejected rows in the routing counters, and "
            "'truncate:N' has no draft head for this layer).")
    first, count = cfg.expert_first, cfg.held_experts
    if not (0 <= first and first + count <= cfg.n_routed_experts
            and count >= 1):
        raise ValueError(
            f"the share of experts [{first}, {first + count}) does not lie "
            f"in the {cfg.n_routed_experts} routed experts")


DECODE, PREFILL = moe_lib.DECODE, moe_lib.PREFILL


def _counter_state(cfg: LongCatFlashConfig):
    return (moe_lib.share_counter_state(cfg.held_experts),)


def routing_stats(cfg: LongCatFlashConfig, state: Tuple[jax.Array, ...]
                  ) -> Dict[str, Any]:
    return moe_lib.share_routing_stats(state[0], cfg.expert_first,
                                       cfg.held_experts)


def _stack(cfg: LongCatFlashConfig, layers: Params, h: jax.Array,
           flat: jax.Array, total: jax.Array, attend, counted: jax.Array):
    """The layers in one scan (``mla.Stack``): layer l's attention block i
    is block ``2l + i`` of the pool."""
    from horovod_tpu.serving import kv_cache as kvc

    def body(carry, xs):
        h, flat, total = carry
        lp, li = xs

        def attend_block(i, x):
            nonlocal flat
            o, flat = attend(flat, 2 * li + i, lp["mla"][i], x)
            return o

        h, counts = layer(cfg, lp, h, attend_block, valid=counted)
        return (h, flat, total + counts), None

    (h, flat, total), _ = lax.scan(body, (h, flat, total),
                                   kvc.with_index(layers))
    return h, flat, total


def decode_body(cfg: LongCatFlashConfig, params: Params, *args):
    """One decode step over all slots (``mla.decode_body``)."""
    return mla.decode_body(cfg, params, *args, stack=_stack)


def prefill_body(cfg: LongCatFlashConfig, params: Params, *args):
    """One prefill chunk of ONE sequence (``mla.prefill_body``)."""
    return mla.prefill_body(cfg, params, *args, stack=_stack)
