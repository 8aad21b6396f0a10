"""Plain reference of the DeepSeek-V3 stack that ``model_type`` ``kimi_k2``
follows (latent attention under a YaRN-stretched rotary, a leading dense
SwiGLU layer, then sigmoid-routed experts plus a shared expert), float32, one
whole sequence at a time: no cache, no kernels, no absorption of ``Wkvb``,
routing by a plain ``top_k``, attention in blocks of queries so that a
sequence of 12 800 positions fits. It imports nothing of the program.
Equations, ``N`` = RMSNorm (eps from the configuration, scale only)::

    h = E[token]
    layer l:  h = h + MLA(N(h));  y = N(h)
              h = h + SwiGLU(y)                       l < first_k_dense_replace
              h = h + MoE(y) + SwiGLU_shared(y)       the others
    logits = N(h) head

    MLA(x):  cq = N(x Wqa);  [q_nope | q_pe] = cq Wqb   per head
             [c | k_pe] = x Wkva;  c = N(c)
             k_pe = yarn_rope(k_pe), one for all heads;  q_pe = yarn_rope(q_pe)
             [k_nope | v] = c Wkvb                      per head
             softmax(causal((q_nope k_nope + q_pe k_pe) * 192^-1/2 * mscale^2)) v  Wo
    yarn_rope: pairs (2i, 2i+1) at inv_freq_i = f_i / factor * (1 - m_i) + f_i m_i,
             f_i = theta^(-2i/D), m_i = 1 - clamp((i - low) / (high - low), 0, 1),
             low / high from beta_fast / beta_slow over original_max_position_embeddings;
             cos and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    MoE(y):  s = sigmoid(y Wr);  chosen = top_k(s + b);  g = scaling s_chosen / sum(s_chosen)
             sum_{chosen e in [first, first + count)} g_e SwiGLU_e(y)

``mscale(s, a) = 0.1 a ln s + 1``. The share: the routed experts ``[first,
first + count)`` are held here; what the routed experts held elsewhere would
add is left out, as in the program, and the shared expert is computed where
the token lives. What ``config.json`` does not say stands under ``assumed``
in the configuration file.

``ops`` supplies the products (``benchmarks.lib.lowprec``), so the control
runs these same lines in a lower precision. A layer is a function of its own
weights alone (``layer``), so a caller whose weights do not fit at once
pushes every sequence through one layer before it draws the next."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
DENSE, MOE = "dense", "moe"
QUERY_BLOCK = 256           # queries a block of the attention


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int
    nope: int
    rope: int
    v: int
    kv_rank: int
    q_rank: int
    n_routed: int               # routed experts the router knows, all chips'
    top_k: int
    scaling: float
    first: int                  # the share held here: [first, first + count)
    count: int
    theta: float
    eps: float
    dense_layers: int           # first_k_dense_replace
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    # (factor, original positions, beta_fast, beta_slow, mscale,
    #  mscale_all_dim)


def dims_of(config: Dict[str, Any], first: int = 0) -> Dims:
    """From a configuration file's keys (the model's public ``config.json``
    names). ``n_routed_experts`` counts the experts held here where the file
    is cut; the router keeps the published count. Refuses what this
    reference does not compute."""
    scaling = config.get("rope_scaling")
    yarn = None
    if scaling is not None:
        if scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling {scaling!r}: only yarn is "
                             f"written down here")
        yarn = (float(scaling["factor"]),
                int(scaling["original_max_position_embeddings"]),
                float(scaling.get("beta_fast", 32)),
                float(scaling.get("beta_slow", 1)),
                float(scaling.get("mscale", 1)),
                float(scaling.get("mscale_all_dim", 0)))
    if (config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]
            or config["n_group"] != 1 or config["topk_group"] != 1):
        raise ValueError("only sigmoid scores renormalised over the chosen, "
                         "in one group, are written down here")
    published = config.get("published", {})
    return Dims(
        heads=config["num_attention_heads"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"], q_rank=config["q_lora_rank"],
        n_routed=published.get("n_routed_experts",
                               config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        scaling=float(config["routed_scaling_factor"]), first=first,
        count=config["n_routed_experts"], theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        dense_layers=config["first_k_dense_replace"], yarn=yarn)


def layer_kind(dims: Dims, layer: int) -> str:
    return DENSE if layer < dims.dense_layers else MOE


def _mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def inv_freq(dims: Dims) -> np.ndarray:
    """The rotary's ``rope / 2`` frequencies, float32, YaRN's where the
    configuration stretches the context."""
    d, f32 = dims.rope, np.float32
    base = f32(dims.theta) ** (np.arange(0, d, 2, dtype=f32) / f32(d))
    if dims.yarn is None:
        return (f32(1.0) / base).astype(f32)
    factor, original, beta_fast, beta_slow, _, _ = dims.yarn

    def correction(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(dims.theta)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=f32) - low) / (high - low), 0, 1)
    keep = f32(1.0) - ramp.astype(f32)
    return ((f32(1.0) / (f32(factor) * base)) * (f32(1.0) - keep)
            + (f32(1.0) / base) * keep).astype(f32)


def cos_sin_scale(dims: Dims) -> float:
    if dims.yarn is None:
        return 1.0
    factor, _, _, _, mscale, mscale_all_dim = dims.yarn
    return _mscale(factor, mscale) / _mscale(factor, mscale_all_dim)


def softmax_scale(dims: Dims) -> float:
    scale = (dims.nope + dims.rope) ** -0.5
    if dims.yarn is not None and dims.yarn[5]:
        scale *= _mscale(dims.yarn[0], dims.yarn[5]) ** 2
    return scale


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(dims: Dims, x, pos):
    """x [S, H, D], pos [S]: pairs (2i, 2i+1) rotated by pos * inv_freq_i."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq(dims))[None]
    m = cos_sin_scale(dims)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(ops, dims: Dims, x, bp):
    """One attention block on one sequence's normed rows x [S, D], the
    queries in blocks of ``QUERY_BLOCK`` (each over every key, causal)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    cq = rmsnorm(ops.einsum("sd,dr->sr", x, bp["wq_a"]), bp["q_norm"],
                 dims.eps)
    q = ops.einsum("sr,ra->sa", cq, bp["wq_b"]).reshape(
        s, dims.heads, dims.nope + dims.rope)
    kv = ops.einsum("sd,dr->sr", x, bp["wkv_a"])
    c = rmsnorm(kv[:, :dims.kv_rank], bp["kv_norm"], dims.eps)
    k_pe = rope(dims, kv[:, None, dims.kv_rank:], pos)          # [S, 1, rope]
    kv_heads = ops.einsum("sr,ra->sa", c, bp["wkv_b"]).reshape(
        s, dims.heads, dims.nope + dims.v)
    k = jnp.concatenate([kv_heads[..., :dims.nope],
                         jnp.broadcast_to(k_pe, (s, dims.heads, dims.rope))],
                        axis=-1)
    q = jnp.concatenate([q[..., :dims.nope],
                         rope(dims, q[..., dims.nope:], pos)], axis=-1)
    v = kv_heads[..., dims.nope:]
    block = min(QUERY_BLOCK, s)
    nb = -(-s // block)
    q = jnp.pad(q, ((0, nb * block - s), (0, 0), (0, 0)))
    scale = softmax_scale(dims)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        qpos = i * block + jnp.arange(block)
        scores = ops.einsum("qhd,khd->hqk", qb, k) * scale
        scores = jnp.where(qpos[None, :, None] >= pos[None, None, :], scores,
                           -jnp.inf)
        return ops.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(one, jnp.arange(nb)).reshape(nb * block, -1)[:s]
    return ops.einsum("sa,ad->sd", o, bp["wo"])


def swiglu(ops, u, w_gate, w_up, w_down):
    a = jax.nn.silu(ops.einsum("sd,df->sf", u, w_gate)) \
        * ops.einsum("sd,df->sf", u, w_up)
    return ops.einsum("sf,fd->sd", a, w_down)


def route(ops, dims: Dims, y, mp):
    """(chosen [S, k] expert ids over the whole router, g [S, k])."""
    s = jax.nn.sigmoid(ops.einsum("sd,de->se", y, mp["router"]))
    _, chosen = jax.lax.top_k(s + mp["router_bias"], dims.top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, dims.scaling * picked / jnp.sum(picked, axis=-1,
                                                   keepdims=True)


def moe(ops, dims: Dims, y, mp, shared: bool = True):
    """The expert half's output for the share ``[first, first + count)``:
    its routed experts' terms and (``shared``) the shared expert's; the
    absent routed experts' terms are left out. The held experts one after
    another, summed as they go."""
    chosen, g = route(ops, dims, y, mp)

    def one(total, j_w):
        j, w_gate, w_up, w_down = j_w
        g_e = jnp.sum(jnp.where(chosen == dims.first + j, g, 0.0), axis=-1)
        return total + g_e[:, None] * swiglu(ops, y, w_gate, w_up,
                                             w_down), None

    s, _ = jax.lax.scan(one, jnp.zeros_like(y),
                        (jnp.arange(dims.count), mp["w_gate"], mp["w_up"],
                         mp["w_down"]))
    if shared:
        sp = mp["shared"]
        s = s + swiglu(ops, y, sp["w_gate"], sp["w_up"], sp["w_down"])
    return s


def attention_half(ops, dims: Dims, h, bp):
    """``h + MLA(N(h))`` on one sequence h [S, D]; ``bp`` the layer's
    attention block's weights."""
    return h + mla(ops, dims, rmsnorm(h, bp["attn_norm"], dims.eps), bp)


def ffn_half(ops, dims: Dims, kind: str, h, fp):
    """``h + SwiGLU(N(h))`` (kind ``dense``) or ``h + MoE(N(h)) +
    SwiGLU_shared(N(h))`` (``moe``); ``fp`` those weights."""
    y = rmsnorm(h, fp["norm"], dims.eps)
    if kind == DENSE:
        return h + swiglu(ops, y, fp["w_gate"], fp["w_up"], fp["w_down"])
    return h + moe(ops, dims, y, fp)


def layer(ops, dims: Dims, kind: str, h, bp, fp):
    """One layer on one sequence: h [S, D]; ``bp`` its attention block's
    weights, ``fp`` its dense SwiGLU's (kind ``dense``) or expert half's
    (``moe``)."""
    return ffn_half(ops, dims, kind, attention_half(ops, dims, h, bp), fp)


def head_logits(ops, dims: Dims, h, final_norm, head):
    """Logits [R, V] of rows h [R, D] of the residual stream."""
    return ops.einsum("sd,dv->sv", rmsnorm(h, final_norm, dims.eps), head)


def logits(ops, dims: Dims, params: Params, tokens, rows) -> jax.Array:
    """Logits [len(rows), V] of one sequence at the positions ``rows``;
    ``params`` as the program's tree (``layers`` with ``mla`` stacked over
    every layer, ``dense`` and ``moe`` over the layers of their kind)."""
    h = params["embed"][tokens]
    layers = params["layers"]
    n = jax.tree.leaves(layers["mla"])[0].shape[0]
    seen = {DENSE: 0, MOE: 0}
    for l in range(n):
        kind = layer_kind(dims, l)
        bp = jax.tree.map(lambda a: a[l], layers["mla"])
        fp = jax.tree.map(lambda a: a[seen[kind]], layers[kind])
        seen[kind] += 1
        h = layer(ops, dims, kind, h, bp, fp)
    return head_logits(ops, dims, h[rows], params["final_norm"],
                       params["head"])
