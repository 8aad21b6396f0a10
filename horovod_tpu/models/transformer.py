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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
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


def _rope(x: jax.Array, pos: jax.Array) -> jax.Array:
    """Rotary embeddings; x [B, S, H, D], pos [S] global positions."""
    d = x.shape[-1]
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]   # [S, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    xr1 = x1 * cos - x2 * sin
    xr2 = x1 * sin + x2 * cos
    out = jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _dense_mlp(cfg: TransformerConfig, h: jax.Array, w_in: jax.Array,
               w_out: jax.Array) -> jax.Array:
    """Dense FFN on local shards. The two d_ff-wide intermediates are
    checkpoint-named so residual dumps (``jax.ad_checkpoint.
    print_saved_residuals``) attribute them, and so name-based policies can
    target them; the selective-recompute wrapper in ``_layer`` (see
    ``TransformerConfig.mlp_recompute``) scopes a nothing-saveable
    checkpoint to exactly this function."""
    from jax.ad_checkpoint import checkpoint_name
    u = checkpoint_name(tp_lib.column_parallel(h, w_in), "mlp_wide")
    u = checkpoint_name(jax.nn.gelu(u), "mlp_wide")
    return tp_lib.row_parallel(u, w_out, cfg.tp_axis)


def _layer(cfg: TransformerConfig, lp: Params, x: jax.Array,
           aux_acc: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One transformer block on local shards. x [b, s_local, D] replicated
    over tp/ep; lp = this layer's (local) params."""
    dt = cfg.dtype
    sp = cfg.sp_axis
    s_local = x.shape[1]
    if sp:
        pos0 = lax.axis_index(sp) * s_local
    else:
        pos0 = 0
    pos = pos0 + jnp.arange(s_local)

    h = _rmsnorm(x, lp["attn_norm"])
    q = tp_lib.column_parallel(h, lp["wq"].astype(dt))
    kk = tp_lib.column_parallel(h, lp["wk"].astype(dt))
    vv = tp_lib.column_parallel(h, lp["wv"].astype(dt))
    hl = q.shape[-1] // cfg.head_dim     # local head count (H / tp)
    shp = (x.shape[0], s_local, hl, cfg.head_dim)
    q, kk, vv = (t.reshape(shp) for t in (q, kk, vv))
    q = _rope(q, pos)
    kk = _rope(kk, pos)
    # hvd_attention / hvd_mlp / hvd_loss: names on the device side of the
    # step (HLO metadata op_name; the backward's operations read
    # transpose(jvp(hvd_attention))). They change nothing computed.
    with jax.named_scope("hvd_attention"):
        if sp and cfg.attention == "ring":
            o = sp_lib.ring_attention(q, kk, vv, sp, causal=True)
        elif sp and cfg.attention == "ulysses":
            o = sp_lib.ulysses_attention(q, kk, vv, sp, causal=True)
        else:
            o = sp_lib.local_attention(q, kk, vv, causal=True)
    o = o.reshape(x.shape[0], s_local, -1)
    attn_out = tp_lib.row_parallel(o, lp["wo"].astype(dt), cfg.tp_axis)
    x = x + attn_out.astype(x.dtype)

    h = _rmsnorm(x, lp["mlp_norm"])
    if cfg.num_experts:
        with jax.named_scope("hvd_mlp"):
            mlp_out, metrics = moe_lib.moe_ffn(
                h, lp["router"], lp["w_in"].astype(dt),
                lp["w_out"].astype(dt), ep_axis=cfg.ep_axis,
                capacity_factor=cfg.capacity_factor)
        aux_acc = aux_acc + metrics.aux_loss
    else:
        mlp_fn = _dense_mlp
        if cfg.mlp_recompute and not cfg.remat:
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
            mlp_fn = jax.checkpoint(
                _dense_mlp, static_argnums=(0,),
                policy=jax.checkpoint_policies.nothing_saveable)
        with jax.named_scope("hvd_mlp"):
            mlp_out = mlp_fn(cfg, h, lp["w_in"].astype(dt),
                             lp["w_out"].astype(dt))
    x = x + mlp_out.astype(x.dtype)
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
