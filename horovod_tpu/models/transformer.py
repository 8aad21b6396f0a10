"""Flagship Transformer LM — exercises every parallelism axis (DP/TP/SP/EP/PP).

The reference framework is model-agnostic data parallelism; its examples stop
at ResNet/MNIST and its parallelism beyond DP is substrate-only (SURVEY §2.4).
This flagship model is where the TPU build goes past the reference: a causal
LM whose forward/backward composes

- DP   — batch sharded over ``dp`` (gradient psum, the Horovod core idea),
- TP   — Megatron-style column/row-parallel projections + vocab-parallel
         embedding/CE over ``tp`` (horovod_tpu.parallel.tensor_parallel),
- SP   — ring attention over ``sp`` (horovod_tpu.parallel.sequence),
- EP   — switch-MoE FFN with AllToAll over ``ep`` (horovod_tpu.parallel.moe),
- PP   — GPipe microbatch rotation over ``pp`` (horovod_tpu.parallel.pipeline),

all inside one shard_map/jit program with static shapes, bf16 matmuls on the
MXU, fp32 residual/softmax/loss.

Designed manual-SPMD: ``forward``/``loss_fn`` run INSIDE shard_map with the
configured axes bound; ``param_specs``/``batch_specs`` give the matching
PartitionSpecs. ``horovod_tpu.parallel.trainer`` wraps this into a jitted
train step; ``__graft_entry__`` uses that for the driver's compile checks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import moe as moe_lib
from horovod_tpu.parallel import pipeline as pp_lib
from horovod_tpu.parallel import sequence as sp_lib
from horovod_tpu.parallel import tensor_parallel as tp_lib

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    head_dim: int = 64
    n_layers: int = 8
    d_ff: int = 2048
    max_seq: int = 2048
    num_experts: int = 0            # 0 = dense FFN; >0 = switch-MoE
    capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    # mesh axis names; None disables that parallelism dimension
    dp_axis: Optional[str] = "dp"
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    ep_axis: Optional[str] = None
    pp_axis: Optional[str] = None
    attention: str = "ring"         # "ring" | "ulysses" (sp_axis set)
    n_microbatches: int = 1         # pipeline microbatches (pp_axis set)
    remat: bool = True              # jax.checkpoint each layer
    # Selective MLP recompute: keep the two d_ff-wide MLP activations
    # (pre-gelu and gelu) out of the saved-residual set and recompute them
    # in the backward from the (d_model-wide) block input — a 4x-narrower
    # save per MLP for one cheap extra matmul + gelu. Full-layer remat
    # (remat=True) was MEASURED losing on v5e (recompute exceeds the
    # saved-activation traffic it avoids, PERF.md r5); this recomputes only
    # the two tensors whose stacking dominated that traffic (~20 ms/step
    # on the 268M LM profile). Ignored when remat=True (strictly coarser).
    mlp_recompute: bool = True
    # Vocab chunk width for the blockwise fused cross-entropy
    # (ops/blockwise_ce): None = HOROVOD_CE_BLOCK_VOCAB knob, 0 = unfused
    # reference CE (materializes [B, S, V_local] logits).
    ce_block_vocab: Optional[int] = None
    # lax.scan unroll over the layer stack. Full unroll (= n_layers) lets
    # XLA assign consistent per-layer layouts, deleting the scan-carry
    # layout-transpose copies — measured +17% tokens/s on the 268M LM on
    # v5e (188 vs 219 ms/step; partial unroll is WORSE than either
    # extreme, PERF.md r5). Costs compile time; 1 = compact loop.
    scan_unroll: int = 1

    @property
    def qkv_dim(self) -> int:
        return self.n_heads * self.head_dim

    def serve_model(self):
        """What :class:`horovod_tpu.serving.ServeEngine` asks of this
        model (``serving.model.ServeModel``)."""
        from horovod_tpu.serving.model import ServeModel
        return ServeModel(
            check=_check_serve, cache_rows=_cache_rows, decode=decode_body,
            prefill=prefill_body, param_specs=param_specs,
            served_params=served_params, draft=_draft_body)


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Params:
    """Global (unsharded) parameter pytree; shard via ``param_specs``."""
    k = iter(jax.random.split(rng, 16))
    d, f, a, v, l = (cfg.d_model, cfg.d_ff, cfg.qkv_dim, cfg.vocab_size,
                     cfg.n_layers)

    def dense(key, shape, scale_dim):
        return (jax.random.normal(key, shape, jnp.float32)
                * (scale_dim ** -0.5)).astype(jnp.float32)

    params: Params = {
        "embed": dense(next(k), (v, d), d),
        "final_norm": jnp.ones((d,), jnp.float32),
        "head": dense(next(k), (d, v), d),
        "layers": {
            "attn_norm": jnp.ones((l, d), jnp.float32),
            "mlp_norm": jnp.ones((l, d), jnp.float32),
            "wq": dense(next(k), (l, d, a), d),
            "wk": dense(next(k), (l, d, a), d),
            "wv": dense(next(k), (l, d, a), d),
            "wo": dense(next(k), (l, a, d), a),
        },
    }
    if cfg.num_experts:
        e = cfg.num_experts
        params["layers"]["router"] = dense(next(k), (l, d, e), d)
        params["layers"]["w_in"] = dense(next(k), (l, e, d, f), d)
        params["layers"]["w_out"] = dense(next(k), (l, e, f, d), f)
    else:
        params["layers"]["w_in"] = dense(next(k), (l, d, f), d)
        params["layers"]["w_out"] = dense(next(k), (l, f, d), f)
    return params


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpecs matching init_params: layer stack over pp, projections
    over tp, experts over ep; everything else replicated."""
    tp, ep, pp = cfg.tp_axis, cfg.ep_axis, cfg.pp_axis
    specs: Params = {
        "embed": P(tp, None),
        "final_norm": P(None),
        "head": P(None, tp),
        "layers": {
            "attn_norm": P(pp, None),
            "mlp_norm": P(pp, None),
            "wq": P(pp, None, tp),
            "wk": P(pp, None, tp),
            "wv": P(pp, None, tp),
            "wo": P(pp, tp, None),
        },
    }
    if cfg.num_experts:
        specs["layers"]["router"] = P(pp, None, None)
        specs["layers"]["w_in"] = P(pp, ep, None, None)
        specs["layers"]["w_out"] = P(pp, ep, None, None)
    else:
        specs["layers"]["w_in"] = P(pp, None, tp)
        specs["layers"]["w_out"] = P(pp, tp, None)
    return specs


def batch_spec(cfg: TransformerConfig) -> P:
    """tokens/labels [B, S]: batch over dp (and ep — expert parallelism
    carries distinct tokens per ep chip, the reference's alltoall dispatch
    pattern), sequence over sp."""
    batch_axes = tuple(a for a in (cfg.dp_axis, cfg.ep_axis) if a)
    if not batch_axes:
        return P(None, cfg.sp_axis)
    return P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
             cfg.sp_axis)


def mesh_axes(cfg: TransformerConfig) -> Tuple[str, ...]:
    return tuple(a for a in (cfg.dp_axis, cfg.tp_axis, cfg.sp_axis,
                             cfg.ep_axis, cfg.pp_axis) if a)


def grad_sync_axes(cfg: TransformerConfig) -> Params:
    """Axes each param's gradient must be psum'ed over — the manual-SPMD
    analogue of Horovod's DistributedOptimizer allreduce (ref
    torch/optimizer.py:36).

    Derivation: our shard_map wrapper disables replication tracking
    (check_vma=False), so lax.psum transposes to its exact global adjoint
    (psum of cotangents). Per-shard reverse AD therefore computes
    g_c = d(sum over ALL chips' loss outputs)/d(this chip's leaf) — exact,
    with no per-path case analysis. Since loss_fn makes the per-chip loss L
    replicated everywhere, the true gradient of L w.r.t. a logical parameter
    is psum of g over every axis the param is REPLICATED on, divided by the
    total number of chips (trainer.sync_gradients applies the 1/W). Sync
    axes thus fall directly out of param_specs: all cfg axes minus the ones
    the leaf is sharded over.
    """
    all_axes = mesh_axes(cfg)

    def axes_for(spec: P) -> Tuple[str, ...]:
        used = set()
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                used.add(a)
        return tuple(a for a in all_axes if a not in used)

    return jax.tree.map(axes_for, param_specs(cfg),
                        is_leaf=lambda x: isinstance(x, P))


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale).astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1`` (1 where
    the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """A rotary stretched past the context it was trained at, by YaRN
    (``rope_scaling`` of type ``yarn`` in a model's ``config.json``; the
    DeepSeek-V3 form). With ``f_i = theta^(-2i/D)`` the frequency of pair
    ``i`` of ``D/2``::

        low  = floor(D ln(original / (2 pi beta_fast)) / (2 ln theta))
        high = ceil (D ln(original / (2 pi beta_slow)) / (2 ln theta))
        m_i  = 1 - clamp((i - low) / (high - low), 0, 1)
        inv_freq_i = f_i / factor * (1 - m_i) + f_i * m_i

    so the fast pairs (``i <= low``) keep their frequency, the slow ones
    (``i >= high``) turn ``factor`` times slower and those between are
    blended on a linear ramp; cos and sin are multiplied by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
    (:attr:`cos_sin_scale`), the softmax scale by ``mscale(factor,
    mscale_all_dim)^2`` (:attr:`softmax_factor`), ``mscale(s, a) = 0.1 a
    ln s + 1``. Everything in float32, computed once from the record
    (:meth:`inv_freq`), never inside a program's arithmetic."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _correction(self, rotations: float, dim: int, theta: float
                    ) -> float:
        return (dim * math.log(self.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    def ramp(self, theta: float, dim: int) -> Tuple[int, int]:
        """(low, high): the pairs up to ``low`` keep their frequency, those
        from ``high`` on are divided by ``factor``."""
        low = math.floor(self._correction(self.beta_fast, dim, theta))
        high = math.ceil(self._correction(self.beta_slow, dim, theta))
        return max(low, 0), min(high, dim - 1)

    def inv_freq(self, theta: float, dim: int) -> np.ndarray:
        """The ``dim / 2`` frequencies, float32."""
        f32 = np.float32
        extra = f32(1.0) / (f32(theta) ** (np.arange(0, dim, 2, dtype=f32)
                                           / f32(dim)))
        inter = f32(1.0) / (f32(self.factor) * f32(theta) ** (
            np.arange(0, dim, 2, dtype=f32) / f32(dim)))
        low, high = self.ramp(theta, dim)
        ramp = np.clip((np.arange(dim // 2, dtype=f32) - f32(low))
                       / f32(max(high - low, 1e-3)), 0, 1).astype(f32)
        keep = f32(1.0) - ramp
        return (inter * (f32(1.0) - keep) + extra * keep).astype(f32)

    @property
    def cos_sin_scale(self) -> float:
        return (_yarn_mscale(self.factor, self.mscale)
                / _yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_factor(self) -> float:
        return (_yarn_mscale(self.factor, self.mscale_all_dim) ** 2
                if self.mscale_all_dim else 1.0)


def rope(x: jax.Array, pos: jax.Array, theta: float = 10000.0,
         heads: int = 1, scaling: Optional[RopeScaling] = None
         ) -> jax.Array:
    """Rotary embedding over the last axis of x, interleaved pairs, in
    float32; the result in the dtype of ``x``. x is ``[..., *pos.shape,
    *H, D]`` with ``heads`` axes ``H`` between the positions' and ``D``: a
    batch ``[B, S, H, D]`` with ``pos[S]``, rows ``[N, H, D]`` with
    ``pos[N]``, a key without heads ``[N, D]`` with ``pos[N]`` and
    ``heads=0``. ``scaling``: the frequencies (and the factor on cos and
    sin) of a stretched context (:class:`RopeScaling`); without one the
    plain ``theta^(-2i/D)``, bit for bit what this function gave before it
    took one. The one rotary of the package: a cached key is bitwise the
    key training rotates."""
    d = x.shape[-1]
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        freqs = jnp.asarray(scaling.inv_freq(theta, d))
    ang = pos[..., None].astype(jnp.float32) * freqs           # [*P, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.cos_sin_scale != 1.0:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    # onto x's axes: size 1 over the leading axes and over the heads
    lead = x.ndim - 1 - heads - pos.ndim
    ones = (*range(lead), *range(x.ndim - 1 - heads, x.ndim - 1))
    cos, sin = lax.expand_dims(cos, ones), lax.expand_dims(sin, ones)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                    axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def visible_softmax(s: jax.Array, visible: jax.Array) -> jax.Array:
    """float32 softmax over the last axis of s ``[N, H, T]`` under visible
    ``[N, T]`` (which keys row n may see); a row that sees nothing (an
    empty slot) gives zeros."""
    vis = visible[:, None, :]
    s = jnp.where(vis, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(vis, jnp.exp(s - m), 0.0)
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def swiglu(cfg: Any, fp: Params, u: jax.Array) -> jax.Array:
    """``(silu(u Wg) * (u Wu)) Wd`` on rows u in ``cfg.dtype``, the gate in
    float32, the result float32: the one SwiGLU of the package (the dense
    FFNs of the shortcut-MoE layer, the shared expert of the hybrid one)."""
    dt = cfg.dtype
    g = jax.nn.silu((u @ fp["w_gate"].astype(dt)).astype(jnp.float32))
    a = (g * (u @ fp["w_up"].astype(dt)).astype(jnp.float32)).astype(dt)
    return jnp.dot(a, fp["w_down"].astype(dt),
                   preferred_element_type=jnp.float32)


def _dense_mlp(cfg: TransformerConfig, h: jax.Array, w_in: jax.Array,
               w_out: jax.Array) -> jax.Array:
    """Dense FFN on local shards. The two d_ff-wide intermediates are
    checkpoint-named so residual dumps (``jax.ad_checkpoint.
    print_saved_residuals``) attribute them, and so name-based policies can
    target them; the selective-recompute wrapper in ``_layer`` (see
    ``TransformerConfig.mlp_recompute``) scopes a nothing-saveable
    checkpoint to exactly this function. (In a forward-only program the
    names lower to nothing.)"""
    from jax.ad_checkpoint import checkpoint_name
    u = checkpoint_name(tp_lib.column_parallel(h, w_in), "mlp_wide")
    u = checkpoint_name(jax.nn.gelu(u), "mlp_wide")
    return tp_lib.row_parallel(u, w_out, cfg.tp_axis)


def block(cfg: TransformerConfig, lp: Params, x: jax.Array, pos: jax.Array,
          attend: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
          mlp: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """THE dense block, on local shards: rows x ``[..., D]`` (a training
    batch ``[b, s_local, D]`` with ``pos[s_local]``, a decode step's
    ``[slots, D]`` with ``pos[slots]``, a prefill chunk's ``[C, D]`` with
    ``pos[C]``); lp = this layer's (local) params.

    ``attend(q, k, v)``, each ``[..., H_local, head_dim]`` with q and k
    rotated, gives the attention's output in that shape: the caller owns
    the attention, and with it the cache. ``mlp(h)`` is the MLP half on the
    normed rows. Training, decode, verify, draft and prefill are this one
    function under different ``attend`` / ``mlp``; it does not know which.

    hvd_attention / hvd_mlp / hvd_kv_write (the callers' scopes) are names
    on the device side of the step (HLO metadata op_name; the backward's
    operations read transpose(jvp(hvd_attention))). They change nothing
    computed. Both norms stand outside them."""
    dt = cfg.dtype
    h = _rmsnorm(x, lp["attn_norm"])
    q = tp_lib.column_parallel(h, lp["wq"].astype(dt))
    k = tp_lib.column_parallel(h, lp["wk"].astype(dt))
    v = tp_lib.column_parallel(h, lp["wv"].astype(dt))
    # [..., H_local, head_dim]: H / tp heads on this shard
    by_head = x.shape[:-1] + (-1, cfg.head_dim)
    q, k, v = (t.reshape(by_head) for t in (q, k, v))
    o = attend(rope(q, pos), rope(k, pos), v)
    o = o.astype(x.dtype).reshape(x.shape[:-1] + (-1,))
    x = x + tp_lib.row_parallel(o, lp["wo"].astype(dt),
                                cfg.tp_axis).astype(x.dtype)
    return x + mlp(_rmsnorm(x, lp["mlp_norm"])).astype(x.dtype)


def _mlp_half(cfg: TransformerConfig, lp: Params,
              mlp_fn: Callable = _dense_mlp) -> Callable:
    """``block``'s ``mlp`` for the dense FFN: ``mlp_fn`` (``_dense_mlp``
    or a checkpointed one) on this layer's weights, under ``hvd_mlp``."""
    def mlp(h):
        with jax.named_scope("hvd_mlp"):
            return mlp_fn(cfg, h, lp["w_in"].astype(cfg.dtype),
                          lp["w_out"].astype(cfg.dtype))
    return mlp


def _layer(cfg: TransformerConfig, lp: Params, x: jax.Array,
           aux_acc: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One training block on local shards. x [b, s_local, D] replicated
    over tp/ep; lp = this layer's (local) params."""
    sp = cfg.sp_axis
    s_local = x.shape[1]
    pos0 = lax.axis_index(sp) * s_local if sp else 0
    pos = pos0 + jnp.arange(s_local)

    def attend(q, k, v):
        with jax.named_scope("hvd_attention"):
            if sp and cfg.attention == "ring":
                return sp_lib.ring_attention(q, k, v, sp, causal=True)
            if sp and cfg.attention == "ulysses":
                return sp_lib.ulysses_attention(q, k, v, sp, causal=True)
            return sp_lib.local_attention(q, k, v, causal=True)

    def moe(h):
        nonlocal aux_acc
        with jax.named_scope("hvd_mlp"):
            out, metrics = moe_lib.moe_ffn(
                h, lp["router"], lp["w_in"].astype(cfg.dtype),
                lp["w_out"].astype(cfg.dtype), ep_axis=cfg.ep_axis,
                capacity_factor=cfg.capacity_factor)
        aux_acc = aux_acc + metrics.aux_loss
        return out

    if cfg.num_experts:
        mlp = moe
    elif cfg.mlp_recompute and not cfg.remat:
        # Checkpoint exactly the d_ff-wide region: its only internals
        # are the two named activations (plus gelu's unnamed wide
        # intermediates, which is why the policy is nothing_saveable
        # rather than save_anything_except_these_names — the latter
        # would keep saving gelu's internals). Inputs (h, weights) stay
        # saved for free; the backward recomputes one [.., d]x[d, 4d]
        # matmul + gelu instead of round-tripping 2 x [.., d_ff] per
        # layer through HBM — the measured middle ground between
        # no-remat (the ~20 ms/step activation-stack traffic) and
        # full-layer remat (recompute-bound, PERF.md r5).
        mlp = _mlp_half(cfg, lp, jax.checkpoint(
            _dense_mlp, static_argnums=(0,),
            policy=jax.checkpoint_policies.nothing_saveable))
    else:
        mlp = _mlp_half(cfg, lp)
    x = block(cfg, lp, x, pos, attend, mlp)
    return x, aux_acc


def _stack_fwd(cfg: TransformerConfig, layers: Params, x: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Scan over the (local) layer stack. layers leaves [L_local, ...]."""
    body = _layer
    if cfg.remat:
        body = jax.checkpoint(body, static_argnums=(0,))

    def step(carry, lp):
        x, aux = carry
        x, aux = body(cfg, lp, x, aux)
        return (x, aux), None

    (x, aux), _ = lax.scan(step, (x, jnp.zeros((), jnp.float32)), layers,
                           unroll=max(int(cfg.scan_unroll), 1))
    return x, aux


def forward(cfg: TransformerConfig, params: Params, tokens: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """Local-shard forward to final hidden states (pre-head).

    tokens [b_local, s_local] int32. Returns (hidden [b, s, D], moe aux loss).
    Must run inside shard_map with cfg's axes bound (or with all axes None,
    plain single-device).
    """
    seq_total = tokens.shape[1]
    if cfg.sp_axis:
        seq_total *= lax.axis_size(cfg.sp_axis)  # tokens arrive seq-sharded
    if seq_total > cfg.max_seq:
        raise ValueError(
            f"sequence length {seq_total} exceeds cfg.max_seq={cfg.max_seq}")
    x = tp_lib.vocab_parallel_embed(tokens, params["embed"].astype(cfg.dtype),
                                    cfg.tp_axis)
    if cfg.pp_axis:
        m = cfg.n_microbatches
        b = x.shape[0]
        if b % m != 0:
            raise ValueError(f"batch {b} not divisible by microbatches {m}")
        x_mb = x.reshape((m, b // m) + x.shape[1:])

        # The MoE aux (load-balance) loss is dropped under pp: threading the
        # scalar through the rotating activation channel would widen every
        # ppermute for a regulariser term. Documented limitation.
        def stage_fn(mb):
            out, _ = _stack_fwd(cfg, params["layers"], mb)
            return out

        x = pp_lib.pipeline_apply(stage_fn, x_mb, cfg.pp_axis)
        x = x.reshape((b,) + x.shape[2:])
        aux = jnp.zeros((), jnp.float32)
    else:
        x, aux = _stack_fwd(cfg, params["layers"], x)
    x = _rmsnorm(x, params["final_norm"])
    return x, aux


def logits_fn(cfg: TransformerConfig, params: Params, tokens: jax.Array
              ) -> jax.Array:
    """Full logits (gathered over tp if sharded) — inference/entry path."""
    x, _ = forward(cfg, params, tokens)
    logits = x @ params["head"].astype(cfg.dtype)
    if cfg.tp_axis:
        logits = lax.all_gather(logits, cfg.tp_axis, axis=-1, tiled=True)
    return logits.astype(jnp.float32)


def loss_fn(cfg: TransformerConfig, params: Params, tokens: jax.Array,
            labels: jax.Array) -> jax.Array:
    """Mean causal-LM cross entropy over ALL tokens in the global batch.

    Runs on local shards; the cross-shard mean is assembled with psums over
    dp/sp so the returned scalar is identical on every chip.
    """
    x, aux = forward(cfg, params, tokens)
    with jax.named_scope("hvd_loss"):
        per_tok = tp_lib.vocab_parallel_cross_entropy(
            x, params["head"].astype(cfg.dtype), labels, cfg.tp_axis,
            block=cfg.ce_block_vocab)
    total = jnp.sum(per_tok)
    count = jnp.full((), per_tok.size, jnp.float32)
    data_axes = [a for a in (cfg.dp_axis, cfg.ep_axis, cfg.sp_axis) if a]
    if cfg.pp_axis:
        # x is pp-replicated (pipeline output broadcast); count each token
        # once by masking all but the last stage, then summing over pp too.
        # This also zeroes head/final_norm cotangents off the last stage so
        # the uniform psum-over-replicated-axes grad sync stays exact.
        last = lax.axis_index(cfg.pp_axis) == lax.axis_size(cfg.pp_axis) - 1
        total = jnp.where(last, total, 0.0)
        count = jnp.where(last, count, 0.0)
        data_axes.append(cfg.pp_axis)
    for ax in data_axes:
        total = lax.psum(total, ax)
        count = lax.psum(count, ax)
    loss = total / count
    if cfg.num_experts:
        aux_mean = aux / max(cfg.n_layers, 1)
        for ax in data_axes:
            aux_mean = lax.pmean(aux_mean, ax)
        loss = loss + cfg.moe_aux_weight * aux_mean
    return loss


# ---------------------------------------------------------------------------
# the serving engine's step bodies (serving.model.ServeModel): the block
# over a paged KV cache. They run per shard, inside shard_map when tp_axis
# is set: heads, FFN and vocabulary are split exactly as in training, and
# the page pool by whole KV heads of its row.
# ---------------------------------------------------------------------------

def _check_serve(cfg: TransformerConfig, draft_mode: str) -> None:
    unsupported = [n for n, a in (("sp", cfg.sp_axis), ("ep", cfg.ep_axis),
                                  ("pp", cfg.pp_axis)) if a]
    if unsupported or cfg.num_experts:
        raise ValueError(
            "serving supports the dense TP/DP transformer only; got "
            f"axes {unsupported or 'none'}, num_experts="
            f"{cfg.num_experts}. Build a serving TransformerConfig with "
            "sp/ep/pp axes None (TP via tp_axis is supported).")


def _cache_rows(cfg: TransformerConfig):
    from horovod_tpu.serving.kv_cache import dense_rows
    return dense_rows(cfg.n_layers, cfg.n_heads, cfg.head_dim)


# What the block and the head cast to ``cfg.dtype`` on the way into a
# product. The norm scales are not among them: ``_rmsnorm`` multiplies the
# scale in float32, so casting it would change the numbers.
_PRODUCT_LEAVES = ("wq", "wk", "wv", "wo", "w_in", "w_out")


def served_params(cfg: TransformerConfig, params: Params) -> Params:
    """The tree with every product's weights in ``cfg.dtype``: what the
    block, the head and the embedding would otherwise cast in every run of
    every program."""
    from horovod_tpu.serving.model import cast_once
    dt, layers = cfg.dtype, params["layers"]
    return {**params,
            "embed": cast_once(params["embed"], dt),
            "head": cast_once(params["head"], dt),
            "layers": {**layers, **{n: cast_once(layers[n], dt)
                                    for n in _PRODUCT_LEAVES}}}


def attend_gathered(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, pos: jax.Array, scale: float
                    ) -> jax.Array:
    """Prefill's attention, in float32: the chunk's queries q ``[C, H, D]``
    at positions ``pos`` over ONE sequence's pages in block-table order
    (``k_pages`` / ``v_pages`` ``[n_phys, page, H*D]``), each query seeing
    the cached positions up to its own: the prefix and the chunk."""
    from horovod_tpu.serving import kv_cache as kvc
    by_head = (-1,) + q.shape[1:]
    kg = kvc.gather_pages(k_pages, block_table).astype(jnp.float32)
    vg = kvc.gather_pages(v_pages, block_table).astype(jnp.float32)
    kg, vg = kg.reshape(by_head), vg.reshape(by_head)
    s = jnp.einsum("chd,shd->chs", q.astype(jnp.float32), kg) * scale
    ctx = jnp.arange(kg.shape[0], dtype=jnp.int32)
    visible = ctx[None, :] <= pos[:, None]           # causal + prefix
    return jnp.einsum("chs,shd->chd", visible_softmax(s, visible), vg)


def _serve_step(cfg: TransformerConfig, params: Params, k_pages: jax.Array,
                v_pages: jax.Array, block_tables: jax.Array,
                tokens: jax.Array, pos: jax.Array, write: Callable,
                attend: Callable, n_layers: Optional[int] = None,
                out_row: Optional[jax.Array] = None):
    """What a decode step and a prefill chunk share: embed ``tokens``
    ``[N]``, the block at positions ``pos`` ``[N]`` over the layers
    (the first ``n_layers``), each writing its K/V rows ``[N, H*D]`` (a
    token's heads side by side: the pool's row) through ``write(pages,
    new, block_tables, scratch)`` and then attending through ``attend(q,
    k_pages, v_pages, block_tables)``, final norm, head (of row
    ``out_row`` only, if given), argmax. Returns ``(k_pages, v_pages,
    next token(s), float32 logits)``.

    The pool is carried through the layer scan as one flat run of pages
    and a layer addressed by offset block tables
    (``kv_cache.block_pages``), so no instruction slices a layer's pool
    out or stacks it back."""
    from horovod_tpu.serving import kv_cache as kvc
    x = tp_lib.vocab_parallel_embed(
        tokens, params["embed"].astype(cfg.dtype), cfg.tp_axis)   # [N, D]
    layers = params["layers"]
    if n_layers is not None:
        layers = jax.tree.map(lambda a: a[:n_layers], layers)

    def layer(carry, xs):
        x, pool = carry
        lp, li = xs
        bt, scratch = kvc.block_pages(k_pages.shape, li, block_tables)

        def attend_cached(q, k, v):
            nonlocal pool
            with jax.named_scope("hvd_kv_write"):
                rows = tuple(t.reshape(t.shape[0], -1) for t in (k, v))
                pool = write(pool, rows, bt, scratch)
            with jax.named_scope("hvd_attention"):
                return attend(q, *pool, bt)

        x = block(cfg, lp, x, pos, attend_cached, _mlp_half(cfg, lp))
        return (x, pool), None

    (x, pool), _ = lax.scan(
        layer, (x, kvc.flat_pool(k_pages, v_pages)), kvc.with_index(layers))
    x = _rmsnorm(x, params["final_norm"])
    if out_row is not None:
        x = jnp.take(x, out_row, axis=0)                           # [D]
    # full-vocab f32 logits (the TP head gathered)
    logits = (x @ params["head"].astype(cfg.dtype)).astype(jnp.float32)
    if cfg.tp_axis:
        logits = lax.all_gather(logits, cfg.tp_axis, axis=-1, tiled=True)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (*(p.reshape(k_pages.shape) for p in pool), next_tokens, logits)


def decode_body(cfg: TransformerConfig, params: Params,
                k_pages: jax.Array, v_pages: jax.Array,
                block_tables: jax.Array, lengths: jax.Array,
                tokens: jax.Array, *, n_layers: Optional[int] = None):
    """One decode step over all slots: tokens ``[S]`` (this step's input
    token per slot), lengths ``[S]`` (tokens already cached — the
    position this token lands at). Empty slots carry length 0 and
    scratch-page block tables; their writes sink into the scratch page
    and their outputs are ignored by the scheduler. Each slot attends over
    its own pages through the paged-decode path (flash kernel on TPU, jnp
    reference elsewhere — ``kv_cache.paged_decode_attention``).

    The SAME body at batch ``slots * (K+1)`` is the speculative verify
    step: each slot's block-table row repeated K+1 times with lengths
    ``len_s .. len_s + K`` and tokens ``[last_accepted, draft_1..K]``
    — every row's K/V lands in the pages BEFORE the layer attends, so
    the ragged-lengths attention gives each row exact causality over
    the drafts that precede it, and row i's argmax is bitwise what
    sequential decode would emit after consuming rows 0..i.

    ``n_layers`` (static) truncates the stack: layers ``0..n-1`` of
    the target plus the shared final norm/head — the self-drafting
    model of the ``truncate:N`` speculative mode. It scans fewer layers
    over the same pool, so layers ``>= n`` are not touched. Its K/V
    writes land in the shared pool; verify recomputes those layers'
    identical values over the same positions and overwrites them, so no
    reader ever observes a draft-only value."""
    from horovod_tpu.serving import kv_cache as kvc
    scale = cfg.head_dim ** -0.5
    # Speculative rows near the context ceiling can carry positions past
    # the last block-table column; the gather would clamp them INTO the
    # request's own last page and corrupt it. Route them to scratch —
    # accepted lengths never reach them, so the value is never read.
    valid = lengths < block_tables.shape[1] * k_pages.shape[2]

    def write(pages, new, bt, scratch):
        return kvc.write_token_rows(pages, new, bt, lengths, valid=valid,
                                    scratch=scratch)

    def attend(q, kp, vp, bt):
        return kvc.paged_decode_attention(q, kp, vp, bt, lengths + 1, scale)

    return _serve_step(cfg, params, k_pages, v_pages, block_tables, tokens,
                       lengths, write, attend, n_layers=n_layers)


def _draft_body(cfg: TransformerConfig, n_layers: int, *args):
    return decode_body(cfg, *args, n_layers=n_layers)


def prefill_body(cfg: TransformerConfig, params: Params,
                 k_pages: jax.Array, v_pages: jax.Array,
                 block_table: jax.Array, start: jax.Array,
                 n_real: jax.Array, tokens: jax.Array):
    """One prefill chunk of ONE sequence: tokens ``[C]`` (bucket-padded),
    positions ``start .. start+n_real`` written to the pages, causal
    attention over the cached prefix + the chunk, last real token's
    logits out. Chunked prefill: a later chunk attends over the earlier
    chunks through the pages it finds already written."""
    from horovod_tpu.serving import kv_cache as kvc
    scale = cfg.head_dim ** -0.5
    pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)

    def write(pages, new, bt, scratch):
        return kvc.write_chunk_pages(pages, new, bt, start, n_real,
                                    scratch=scratch)

    def attend(q, kp, vp, bt):
        return attend_gathered(q, kp, vp, bt, pos, scale)

    return _serve_step(cfg, params, k_pages, v_pages, block_table, tokens,
                       pos, write, attend,
                       out_row=jnp.maximum(n_real - 1, 0))


class TransformerLM:
    """Thin OO wrapper pairing a config with init/apply (flax-like surface)."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def init(self, rng: jax.Array) -> Params:
        return init_params(self.cfg, rng)

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        return logits_fn(self.cfg, params, tokens)

    def loss(self, params: Params, tokens: jax.Array,
             labels: jax.Array) -> jax.Array:
        return loss_fn(self.cfg, params, tokens, labels)
