"""Plain reference of LongCat-Flash's layer (two latent-attention blocks, two
dense SwiGLU FFNs and one expert block on a shortcut), float32, one whole
sequence at a time: no cache, no kernels, no absorption of ``Wkvb``, routing
by a plain ``top_k``. It imports nothing of the program. Equations, ``N`` =
RMSNorm (eps from the configuration, scale only), for i in 0, 1::

    h = h + MLA_i(N(h))
    u = N(h)
    if i == 0:  s = MoE(u)
    h = h + (silu(u Wg_i) * (u Wu_i)) Wd_i
    if i == 1:  h = h + s
    logits = N(h) head

    MLA(x):  cq = N(x Wqa);  [q_nope | q_rope] = (cq Wqb) a_q  per head
             [c | k_r] = x Wkva;  c = N(c) a_kv;  k_r = rope(k_r), one for all heads
             [k_nope | v] = c Wkvb  per head
             softmax(causal((q_nope k_nope + rope(q_rope) k_r) / sqrt(nope + rope))) v  Wo
    MoE(u):  p = softmax(u Wr);  chosen = top_k(p + b);  g_e = scaling p_e
             sum_{chosen e in [first, first + count)} g_e SwiGLU_e(u)
               + sum_{chosen e >= n_routed} g_e u

``a_q = sqrt(hidden / q_lora_rank)``, ``a_kv = sqrt(hidden / kv_lora_rank)``;
rotary over the rope dimensions, interleaved pairs. The share: the routed
experts ``[first, first + count)`` are held here; what the routed experts held
elsewhere would add is left out, as in the program, and the identity
(zero-compute) experts' terms are computed where the token lives. What the
configuration's ``config.json`` does not say (the scale formulas, the pairing,
that the top-k weights are not renormalised, a zero routing bias) stands
under ``assumed`` in the configuration file.

``ops`` supplies the products (``benchmarks.lib.lowprec``), so the control
runs these same lines in a lower precision. A layer is a function of its own
weights alone (``layer``), so a caller whose weights do not fit at once
pushes every sequence through one layer before it draws the next."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Dims:
    heads: int
    nope: int
    rope: int
    v: int
    kv_rank: int
    q_rank: int
    hidden: int
    n_routed: int               # routed experts the router knows, all chips'
    top_k: int
    scaling: float
    first: int                  # the share held here: [first, first + count)
    count: int
    theta: float
    eps: float
    scale_q: bool = True
    scale_kv: bool = True


def dims_of(config: Dict[str, Any], first: int = 0) -> Dims:
    """From a configuration file's keys (the model's public ``config.json``
    names). ``n_routed_experts`` counts the experts held here where the file
    is cut; the router keeps the published count."""
    published = config.get("published", {})
    return Dims(
        heads=config["num_attention_heads"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"], q_rank=config["q_lora_rank"],
        hidden=config["hidden_size"],
        n_routed=published.get("n_routed_experts",
                               config["n_routed_experts"]),
        top_k=config["moe_topk"],
        scaling=float(config["routed_scaling_factor"]), first=first,
        count=config["n_routed_experts"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        scale_q=bool(config["mla_scale_q_lora"]),
        scale_kv=bool(config["mla_scale_kv_lora"]))


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x [S, H, D], pos [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(ops, dims: Dims, x, bp):
    """One attention block on one sequence's normed rows x [S, D]."""
    s = x.shape[0]
    pos = jnp.arange(s)
    a_q = (dims.hidden / dims.q_rank) ** 0.5 if dims.scale_q else 1.0
    a_kv = (dims.hidden / dims.kv_rank) ** 0.5 if dims.scale_kv else 1.0
    cq = rmsnorm(ops.einsum("sd,dr->sr", x, bp["wq_a"]), bp["q_norm"],
                 dims.eps)
    q = (ops.einsum("sr,ra->sa", cq, bp["wq_b"]) * a_q).reshape(
        s, dims.heads, dims.nope + dims.rope)
    kv = ops.einsum("sd,dr->sr", x, bp["wkv_a"])
    c = rmsnorm(kv[:, :dims.kv_rank], bp["kv_norm"], dims.eps) * a_kv
    k_r = rope(kv[:, None, dims.kv_rank:], pos, dims.theta)      # [S, 1, rope]
    kv_heads = ops.einsum("sr,ra->sa", c, bp["wkv_b"]).reshape(
        s, dims.heads, dims.nope + dims.v)
    k = jnp.concatenate([kv_heads[..., :dims.nope],
                         jnp.broadcast_to(k_r, (s, dims.heads, dims.rope))],
                        axis=-1)
    q = jnp.concatenate([q[..., :dims.nope],
                         rope(q[..., dims.nope:], pos, dims.theta)], axis=-1)
    scores = ops.einsum("qhd,khd->hqk", q, k) \
        * (dims.nope + dims.rope) ** -0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = ops.einsum("hqk,khd->qhd", probs, kv_heads[..., dims.nope:])
    return ops.einsum("sa,ad->sd", o.reshape(s, -1), bp["wo"])


def swiglu(ops, u, w_gate, w_up, w_down):
    a = jax.nn.silu(ops.einsum("sd,df->sf", u, w_gate)) \
        * ops.einsum("sd,df->sf", u, w_up)
    return ops.einsum("sf,fd->sd", a, w_down)


def route(ops, dims: Dims, u, mp):
    """(chosen [S, k] expert ids over the whole router, g [S, k])."""
    p = jax.nn.softmax(ops.einsum("sd,de->se", u, mp["router"]), axis=-1)
    _, chosen = jax.lax.top_k(p + mp["router_bias"], dims.top_k)
    return chosen, dims.scaling * jnp.take_along_axis(p, chosen, axis=-1)


def moe(ops, dims: Dims, u, mp, identity: bool = True):
    """The expert block's output for the share ``[first, first + count)``:
    its routed experts' terms and (``identity``) the zero-compute experts'
    terms; the absent routed experts' terms are left out."""
    chosen, g = route(ops, dims, u, mp)

    def one(j_w):
        j, w_gate, w_up, w_down = j_w
        g_e = jnp.sum(jnp.where(chosen == dims.first + j, g, 0.0), axis=-1)
        return g_e[:, None] * swiglu(ops, u, w_gate, w_up, w_down)

    s = jnp.sum(jax.lax.map(one, (jnp.arange(dims.count), mp["w_gate"],
                                  mp["w_up"], mp["w_down"])), axis=0)
    if identity:
        g_zero = jnp.sum(jnp.where(chosen >= dims.n_routed, g, 0.0), axis=-1)
        s = s + g_zero[:, None] * u
    return s


def layer(ops, dims: Dims, h, lp):
    """One layer on one sequence: h [S, D]; ``lp`` one layer's weights,
    ``{"mla": (block 0, block 1), "ffn": (.., ..), "moe": ..}``."""
    s = None
    for i in (0, 1):
        bp, fp = lp["mla"][i], lp["ffn"][i]
        h = h + mla(ops, dims, rmsnorm(h, bp["attn_norm"], dims.eps), bp)
        u = rmsnorm(h, fp["ffn_norm"], dims.eps)
        if i == 0:
            s = moe(ops, dims, u, lp["moe"])
        h = h + swiglu(ops, u, fp["w_gate"], fp["w_up"], fp["w_down"])
    return h + s


def head_logits(ops, dims: Dims, h, final_norm, head):
    """Logits [R, V] of rows h [R, D] of the residual stream."""
    return ops.einsum("sd,dv->sv", rmsnorm(h, final_norm, dims.eps), head)


def logits(ops, dims: Dims, params: Params, tokens, rows) -> jax.Array:
    """Logits [len(rows), V] of one sequence at the positions ``rows``;
    ``params`` as the program's tree, every per-layer leaf stacked."""
    h = params["embed"][tokens]
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for l in range(n_layers):
        h = layer(ops, dims, h,
                  jax.tree.map(lambda a: a[l], params["layers"]))
    return head_logits(ops, dims, h[rows], params["final_norm"],
                       params["head"])
