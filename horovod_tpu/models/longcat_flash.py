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

MLA (multi-head latent attention, low-rank q and kv, no biases):
``cq = N(x Wqa)``; ``[q_nope | q_rope] = (cq Wqb) a_q`` per head;
``[c | k_r] = x Wkva``; ``c = N(c) a_kv``; ``k_r = rope(k_r)`` (one rotary
key shared by every head); ``[k_nope | v] = c Wkvb`` per head;
``score = (q_nope k_nope + rope(q_rope) k_r) / sqrt(nope + rope)``, causal
softmax in float32, ``o = concat(p v) Wo``. **The cache holds, per token and
attention block, ``c`` (after norm and scale) and ``k_r`` (after rotary)**:
``kv_lora_rank + rope`` numbers. A prefill chunk expands ``k_nope`` and ``v``
from the cached ``c``; a decode step absorbs ``Wkvb`` (``q_lat = q_nope
Wkvb_k^T``, ``out = (p c) Wkvb_v``) and reads only the latent rows.

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

from horovod_tpu.models.transformer import (
    _rmsnorm, rope, swiglu, visible_softmax)
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

    @property
    def held_experts(self) -> int:
        return (self.n_routed_experts if self.expert_count is None
                else self.expert_count)

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
            check=_check_serve, cache_rows=_cache_rows, decode=decode_body,
            prefill=prefill_body, param_specs=param_specs,
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

def _norm(cfg, x, scale):
    """RMSNorm of the residual stream (float32) or of a latent, in float32;
    the result in the dtype of ``x``."""
    return _rmsnorm(x, scale, eps=cfg.norm_eps)


def mla_project(cfg: LongCatFlashConfig, bp: Params, x: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The low-rank projections of one attention block on normed rows x
    ``[N, D]`` at positions ``pos``: (q_nope ``[N, H, nope]``, q_rope
    ``[N, H, rope]`` rotated, the cache row ``[N, kv_lora_rank + rope]`` =
    ``c`` after norm and scale beside ``k_r`` after rotary)."""
    dt = cfg.dtype
    n = x.shape[0]
    cq = _norm(cfg, x @ bp["wq_a"].astype(dt), bp["q_norm"])
    q = ((cq @ bp["wq_b"].astype(dt)) * cfg.a_q).astype(dt)
    q = q.reshape(n, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    kv = x @ bp["wkv_a"].astype(dt)
    c = (_norm(cfg, kv[:, :cfg.kv_lora_rank], bp["kv_norm"])
         * cfg.a_kv).astype(dt)
    k_r = rope(kv[:, cfg.kv_lora_rank:], pos, cfg.rope_theta, heads=0)
    q_rope = rope(q_rope, pos, cfg.rope_theta)
    return q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1)


def _wkv_b(cfg, bp):
    """``Wkvb`` by head: (``[rank, H, nope]`` to keys, ``[rank, H, v]`` to
    values)."""
    w = bp["wkv_b"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def mla_attend_absorbed(cfg: LongCatFlashConfig, bp: Params,
                        q_nope: jax.Array, q_rope: jax.Array,
                        rows: jax.Array, visible: jax.Array) -> jax.Array:
    """Decode's attention: each query row n over ITS OWN cached rows
    ``rows[n]`` ``[T, kv_lora_rank + rope]`` with ``Wkvb`` absorbed into the
    query and the output, so what is read per cached token is the latent
    row and never the heads' keys and values. Returns ``[N, H * v]``."""
    dt = cfg.dtype
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    wk, wv = _wkv_b(cfg, bp)
    c, k_r = rows[..., :cfg.kv_lora_rank], rows[..., cfg.kv_lora_rank:]
    with jax.named_scope("hvd_mla_proj"):
        q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, wk).astype(dt)
    with jax.named_scope("hvd_attention"):
        s = (jnp.einsum("nhr,ntr->nht", q_lat, c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nhd,ntd->nht", q_rope, k_r,
                          preferred_element_type=jnp.float32)) * scale
        p = visible_softmax(s, visible).astype(dt)
        o_lat = jnp.einsum("nht,ntr->nhr", p, c).astype(dt)
    with jax.named_scope("hvd_mla_proj"):
        o = jnp.einsum("nhr,rhv->nhv", o_lat, wv)
    return o.reshape(o.shape[0], -1).astype(dt)


def mla_attend_expanded(cfg: LongCatFlashConfig, bp: Params,
                        q_nope: jax.Array, q_rope: jax.Array,
                        rows: jax.Array, visible: jax.Array) -> jax.Array:
    """Prefill's attention: every query row over ONE sequence's cached rows
    ``[T, kv_lora_rank + rope]``, the heads' keys and values expanded from
    ``c``. Returns ``[N, H * v]``."""
    dt = cfg.dtype
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    wk, wv = _wkv_b(cfg, bp)
    c, k_r = rows[:, :cfg.kv_lora_rank], rows[:, cfg.kv_lora_rank:]
    with jax.named_scope("hvd_mla_proj"):
        k_nope = jnp.einsum("tr,rhd->thd", c, wk).astype(dt)
        v = jnp.einsum("tr,rhv->thv", c, wv).astype(dt)
    with jax.named_scope("hvd_attention"):
        s = (jnp.einsum("nhd,thd->nht", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nhd,td->nht", q_rope, k_r,
                          preferred_element_type=jnp.float32)) * scale
        p = visible_softmax(s, visible).astype(dt)
        o = jnp.einsum("nht,thv->nhv", p, v)
    return o.reshape(o.shape[0], -1).astype(dt)


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


def logits_of(cfg: LongCatFlashConfig, params: Params, h: jax.Array
              ) -> jax.Array:
    x = _norm(cfg, h, params["final_norm"]).astype(cfg.dtype)
    return jnp.dot(x, params["head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


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


def _cache_rows(cfg: LongCatFlashConfig):
    from horovod_tpu.serving.kv_cache import CacheRows
    return (CacheRows("latent", 2 * cfg.n_layers, (cfg.cache_row,)),)


DECODE, PREFILL = moe_lib.DECODE, moe_lib.PREFILL


def _counter_state(cfg: LongCatFlashConfig):
    return (moe_lib.share_counter_state(cfg.held_experts),)


def routing_stats(cfg: LongCatFlashConfig, state: Tuple[jax.Array, ...]
                  ) -> Dict[str, Any]:
    return moe_lib.share_routing_stats(state[0], cfg.expert_first,
                                       cfg.held_experts)


def _serve_step(cfg: LongCatFlashConfig, params: Params, pool: jax.Array,
                counters: jax.Array, block_tables: jax.Array,
                tokens: jax.Array, pos: jax.Array, counted: jax.Array,
                write, mla_attend, program: int,
                out_row: Optional[jax.Array] = None):
    """What a decode step and a prefill chunk share: embed ``tokens``
    ``[N]``, the layers at positions ``pos`` ``[N]`` through the latent
    cache (pool ``[2L, P+1, page, row]``: layer l's attention block i is
    block ``2l + i``), each block writing its rows through ``write(pages,
    new, block_tables, scratch)`` and attending with ``mla_attend`` over
    the gathered pages, each row seeing the cached positions up to its
    own; the head (of row ``out_row`` only, if given), argmax. The rows
    ``counted`` go into ``program``'s routing counters."""
    from horovod_tpu.serving import kv_cache as kvc
    n_ctx = block_tables.shape[-1] * pool.shape[2]
    visible = jnp.arange(n_ctx, dtype=jnp.int32)[None, :] <= pos[:, None]
    h = params["embed"][tokens].astype(jnp.float32)                 # [N, D]
    flat, = kvc.flat_pool(pool)

    def body(carry, xs):
        h, flat, total = carry
        lp, li = xs

        def attend(i, x):
            nonlocal flat
            bt, scratch = kvc.block_pages(pool.shape, 2 * li + i,
                                          block_tables)
            with jax.named_scope("hvd_mla_proj"):
                q_nope, q_rope, row = mla_project(cfg, lp["mla"][i], x, pos)
            with jax.named_scope("hvd_kv_write"):
                flat, = write((flat,), (row,), bt, scratch)
            with jax.named_scope("hvd_attention"):
                rows = kvc.gather_pages(flat, bt)   # [(N,) n_ctx, row]
            return mla_attend(cfg, lp["mla"][i], q_nope, q_rope, rows,
                              visible)

        h, counts = layer(cfg, lp, h, attend, valid=counted)
        return (h, flat, total + counts), None

    zero = jnp.zeros((counters.shape[-1],), jnp.int32)
    (h, flat, total), _ = lax.scan(body, (h, flat, zero),
                                   kvc.with_index(params["layers"]))
    if out_row is not None:
        h = jnp.take(h, out_row, axis=0)                            # [D]
    logits = logits_of(cfg, params, h)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (flat.reshape(pool.shape),
            moe_lib.add_share_counts(counters, total, program),
            next_tokens, logits)


def decode_body(cfg: LongCatFlashConfig, params: Params, pool: jax.Array,
                counters: jax.Array, block_tables: jax.Array,
                lengths: jax.Array, tokens: jax.Array):
    """One decode step over all slots through the latent cache, ``Wkvb``
    absorbed (each slot over its own pages). Empty slots carry length 0
    and scratch block tables; their rows sink into the scratch page and
    are not counted (a served slot has its prompt cached)."""
    from horovod_tpu.serving import kv_cache as kvc
    valid = lengths < block_tables.shape[1] * pool.shape[2]

    def write(pages, new, bt, scratch):
        return kvc.write_token_rows(pages, new, bt, lengths, valid=valid,
                                    scratch=scratch)

    return _serve_step(cfg, params, pool, counters, block_tables, tokens,
                       lengths, lengths > 0, write, mla_attend_absorbed,
                       DECODE)


def prefill_body(cfg: LongCatFlashConfig, params: Params, pool: jax.Array,
                 counters: jax.Array, block_table: jax.Array,
                 start: jax.Array, n_real: jax.Array, tokens: jax.Array):
    """One prefill chunk of ONE sequence: tokens ``[C]`` (bucket-padded) at
    positions ``start ..``, their latent rows written to the pages, causal
    attention over the cached prefix + the chunk (keys and values expanded
    from the cached rows), the last real token's logits out."""
    from horovod_tpu.serving import kv_cache as kvc
    c = tokens.shape[0]
    pos = start + jnp.arange(c, dtype=jnp.int32)

    def write(pages, new, bt, scratch):
        return kvc.write_chunk_rows(pages, new, bt, start, n_real,
                                    scratch=scratch)

    return _serve_step(cfg, params, pool, counters, block_table, tokens,
                       pos, jnp.arange(c) < n_real, write,
                       mla_attend_expanded, PREFILL,
                       out_row=jnp.maximum(n_real - 1, 0))
