"""A DeepSeek-V3 layer stack (Kimi K2's: ``model_type`` ``kimi_k2``) as the
serving engine runs it: latent attention (MLA) under a YaRN-stretched rotary
in every layer, a dense SwiGLU in the first ``first_k_dense`` layers and
sigmoid-routed experts plus a shared expert in the rest.

``h`` the residual stream (float32), ``N`` = RMSNorm (scale only)::

    h0 = E[token]
    x = N(h);  h = h + MLA(x)                        every layer
    y = N(h);  h = h + SwiGLU_dense(y)               layer l < first_k_dense
               h = h + MoE(y) + SwiGLU_shared(y)     the others
    logits = N(h) W_head                             the slice of the untied head

    MLA:     models/mla.py, with no latent scaling (a_q = a_kv = 1), the
             rotary of rope_theta stretched by YaRN (transformer.RopeScaling)
             and the softmax scale 1 / sqrt(nope + rope) times YaRN's
             mscale(factor, mscale_all_dim)^2
    MoE(y):  s = sigmoid(y Wr) in float32 over every routed expert;
             the top-k of s + b (b: the learned correction bias);
             g = scaling * s_chosen / sum(s_chosen);  sum_k g_k SwiGLU_{e_k}(y)

The cache is the latent cache of :mod:`~horovod_tpu.models.mla`: one latent
row a token and layer, ``n_layers`` blocks. The layers of one kind are
stacked (``layers["dense"]``, ``layers["moe"]``; the attention blocks of all
of them under ``layers["mla"]``) and a step scans each run of like layers
(``granite_hybrid.LayerStack``), slicing a layer out of its run; the expert
half is ``granite_hybrid.experts`` with this model's router. The share of the
routed experts held here is ``expert_first`` / ``expert_count``
(:mod:`horovod_tpu.parallel.moe`): the router keeps its width and its experts
per token, the absent experts' terms are left out, and the shared expert is
computed where the token lives. Serving only."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import granite_hybrid as stack_lib
from horovod_tpu.models import mla
from horovod_tpu.models.granite_hybrid import LayerStack
from horovod_tpu.models.transformer import RopeScaling, swiglu
from horovod_tpu.parallel import moe as moe_lib

Params = Dict[str, Any]

DENSE, MOE = "dense", "moe"

# Kimi-K2's rope_scaling as published: yarn, factor 64 over 4096 positions
KIMI_K2_YARN = RopeScaling(factor=64.0, original_max_position=4096,
                           beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                           mscale_all_dim=1.0)


@dataclasses.dataclass(frozen=True)
class KimiK2Config(LayerStack):
    """Sizes of the stack (defaults: Kimi-K2 as published) and the share of
    its routed experts held here; ``serve_model()`` is what
    ``ServeEngine`` asks for."""
    vocab_size: int = 163840
    d_model: int = 7168
    n_layers_total: int = 61
    first_k_dense: int = 1
    d_ff: int = 18432               # the dense layers' SwiGLU width
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    n_routed_experts: int = 384     # the router's outputs, all chips'
    top_k: int = 8
    routed_scaling: float = 2.827
    d_expert: int = 2048            # a routed expert's SwiGLU width
    d_shared: int = 2048            # the shared expert's
    # the share of the routed experts this chip holds
    expert_first: int = 0
    expert_count: Optional[int] = None      # None: all of them
    rope_theta: float = 50000.0
    rope_scaling: Optional[RopeScaling] = KIMI_K2_YARN
    norm_eps: float = 1e-5
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16
    tp_axis: Optional[str] = None   # not offered: one chip's share is served
    # neither latent is scaled, and the shared expert half
    # (granite_hybrid.experts) adds at 1: constants of the class
    a_q = a_kv = 1.0
    residual_multiplier = 1.0

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(DENSE if i < self.first_k_dense else MOE
                     for i in range(self.n_layers_total))

    @property
    def attention_blocks(self) -> int:
        """Cached blocks: one attention block a layer."""
        return self.n_layers

    @property
    def cache_row(self) -> int:
        """Numbers one token caches in one attention block."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        if self.rope_scaling is not None:
            scale *= self.rope_scaling.softmax_factor
        return scale

    def route(self, v: jax.Array, ep: Params) -> moe_lib.TopKRouting:
        """A sigmoid an output, the chosen renormalised and scaled."""
        return moe_lib.topk_sigmoid_route(
            v, ep["router"], ep["router_bias"], self.top_k,
            self.routed_scaling)

    def serve_model(self):
        """What :class:`horovod_tpu.serving.ServeEngine` asks of this
        model (``serving.model.ServeModel``)."""
        from horovod_tpu.serving.model import ServeModel
        return ServeModel(
            check=_check_serve, cache_rows=mla.cache_rows,
            decode=decode_body, prefill=prefill_body,
            param_specs=param_specs, state=_counter_state,
            stats=routing_stats)


def param_shapes(cfg: KimiK2Config) -> Params:
    """Shape and fan-in of every leaf (``None`` fan-in: a norm scale or the
    routing bias), in the tree ``init_params`` returns."""
    d, h, l = cfg.d_model, cfg.n_heads, cfg.n_layers
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
    ld, lm = cfg.count(DENSE), cfg.count(MOE)
    e, f, fe, fs = cfg.held_experts, cfg.d_ff, cfg.d_expert, cfg.d_shared
    attention = {"attn_norm": ((l, d), None),
                 "wq_a": ((l, d, rq), d), "q_norm": ((l, rq), None),
                 "wq_b": ((l, rq, h * (dn + dr)), rq),
                 "wkv_a": ((l, d, rkv + dr), d), "kv_norm": ((l, rkv), None),
                 "wkv_b": ((l, rkv, h * (dn + dv)), rkv),
                 "wo": ((l, h * dv, d), h * dv)}
    dense = {"norm": ((ld, d), None), "w_gate": ((ld, d, f), d),
             "w_up": ((ld, d, f), d), "w_down": ((ld, f, d), f)}
    moe = {"norm": ((lm, d), None),
           "router": ((lm, d, cfg.n_routed_experts), d),
           "router_bias": ((lm, cfg.n_routed_experts), None),
           "w_gate": ((lm, e, d, fe), d), "w_up": ((lm, e, d, fe), d),
           "w_down": ((lm, e, fe, d), fe),
           "shared": {"w_gate": ((lm, d, fs), d), "w_up": ((lm, d, fs), d),
                      "w_down": ((lm, fs, d), fs)}}
    return {"embed": ((cfg.vocab_size, d), d), "final_norm": ((d,), None),
            "head": ((d, cfg.vocab_size), d),
            "layers": {"mla": attention, DENSE: dense, MOE: moe}}


def init_params(cfg: KimiK2Config, rng: jax.Array, dtype: Any = None
                ) -> Params:
    """``granite_hybrid.init_tree`` of this model's leaves: products ~ N(0,
    1 / fan_in) in ``dtype``, the router float32, norm scales 1, the
    router's correction bias 0."""
    return stack_lib.init_tree(param_shapes(cfg), rng, dtype or cfg.dtype,
                               1, zeros=("router_bias",))


def param_specs(cfg: KimiK2Config) -> Params:
    """Every leaf replicated: this module serves one chip's share."""
    return jax.tree.map(lambda sf: P(*([None] * len(sf[0]))),
                        param_shapes(cfg), is_leaf=stack_lib._is_shape)


# ---------------------------------------------------------------------------
# the serving engine's records and step bodies (serving.model.ServeModel)
# ---------------------------------------------------------------------------

def _check_serve(cfg: KimiK2Config, draft_mode: str) -> None:
    if cfg.tp_axis or draft_mode != "off":
        raise ValueError(
            "serving supports the latent-attention expert stack on one "
            f"chip's share with plain decode only; got tp_axis="
            f"{cfg.tp_axis!r}, draft mode {draft_mode!r}. Build it with "
            "tp_axis None and HOROVOD_SERVE_DRAFT=off (a verify step would "
            "count its rejected rows in the routing counters, and "
            "'truncate:N' has no draft head for this stack).")
    if not cfg.count(MOE):
        raise ValueError(
            f"first_k_dense={cfg.first_k_dense} leaves no expert layer among "
            f"the {cfg.n_layers_total}")
    first, count = cfg.expert_first, cfg.held_experts
    if not (0 <= first and first + count <= cfg.n_routed_experts
            and count >= 1):
        raise ValueError(
            f"the share of experts [{first}, {first + count}) does not lie "
            f"in the {cfg.n_routed_experts} routed experts")


def _counter_state(cfg: KimiK2Config):
    return (moe_lib.share_counter_state(cfg.held_experts),)


def routing_stats(cfg: KimiK2Config, state: Tuple[jax.Array, ...]
                  ) -> Dict[str, Any]:
    return moe_lib.share_routing_stats(state[0], cfg.expert_first,
                                       cfg.held_experts)


def _stack(cfg: KimiK2Config, layers: Params, h: jax.Array, flat: jax.Array,
           total: jax.Array, attend, counted: jax.Array):
    """Every run of like layers in a scan of its own (``mla.Stack``):
    layer l's attention block is block l of the pool; the dense SwiGLU
    under ``hvd_mlp``, the expert half through ``granite_hybrid.experts``
    (routed experts under ``hvd_moe``, the shared one under ``hvd_mlp``)."""
    dt = cfg.dtype

    def run_of(kind):
        def body(carry, index):
            h, flat, total = carry
            li, ki = index
            bp = jax.tree.map(lambda a: a[li], layers["mla"])
            o, flat = attend(flat, li, bp,
                             mla.norm(cfg, h, bp["attn_norm"]).astype(dt))
            with jax.named_scope("hvd_mla_proj"):
                h = h + jnp.dot(o, bp["wo"].astype(dt),
                                preferred_element_type=jnp.float32)
            fp = jax.tree.map(lambda a: a[ki], layers[kind])
            if kind == DENSE:
                y = mla.norm(cfg, h, fp["norm"]).astype(dt)
                with jax.named_scope("hvd_mlp"):    # the norm outside it
                    h = h + swiglu(cfg, fp, y)
            else:
                h, counts = stack_lib.experts(cfg, fp, h, counted)
                total = total + counts
            return (h, flat, total), None
        return body

    carry = (h, flat, total)
    for kind, first, first_of_kind, n in cfg.runs():
        steps = jnp.arange(n, dtype=jnp.int32)
        carry, _ = lax.scan(run_of(kind), carry,
                            (first + steps, first_of_kind + steps))
    return carry


def decode_body(cfg: KimiK2Config, params: Params, *args):
    """One decode step over all slots (``mla.decode_body``)."""
    return mla.decode_body(cfg, params, *args, stack=_stack)


def prefill_body(cfg: KimiK2Config, params: Params, *args):
    """One prefill chunk of ONE sequence (``mla.prefill_body``)."""
    return mla.prefill_body(cfg, params, *args, stack=_stack)
