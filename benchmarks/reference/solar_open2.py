"""Plain reference of the delta-rule hybrid stack (Solar Open 2's layer):
gated delta-rule linear-attention layers (Kimi Delta Attention, KDA,
arXiv:2510.26692) with a gated grouped-query attention layer without
positional embedding among every few, each followed by sigmoid-routed
experts plus a shared expert. float32, one whole sequence at a time: no
cache, no slot state, no kernels; the delta rule TOKEN BY TOKEN (never the
chunked form), a plain loop over the held experts, the full causal
attention. It imports nothing of the program. Equations, ``N`` = RMSNorm
(eps from the configuration, scale only), ``d`` the head size::

    h0 = E[token]
    u = N(h);  h = h + Mixer(u)                Mixer = GQA if the layer is in
    v = N(h);  h = h + MoE(v) + Shared(v)      ``gqa_layers`` else KDA
    logits = N(h) W_head                        the slice of the untied head

    KDA(u):  q = silu(conv(u W_q)), k = silu(conv(u W_k)), v = silu(conv(u W_v))
                                                each [H, d]; depthwise, causal,
                                                width K, zeros before t = 0,
                                                no bias
             q^ = q / sqrt(|q|^2 + 1e-6) * d^(-1/2);  k^ = k / sqrt(|k|^2 + 1e-6)
             a_t = -exp(A_log[h]) * softplus((u W_f1) W_f2 + dt_bias)   [H, d]
             b_t = 2 * sigmoid(u W_b)           [H]
             S' = exp(a_t)[:, None] * S_{t-1};  S_{-1} = 0;  S[h] in R^{d x d}
             S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T
             o_t = S_t^T q^_t
             out = (N_w(o_t) per head * sigmoid((u W_g1) W_g2)) W_o
    GQA(u):  q = u Wq [H, d], z = u Wz [H, d], k = u Wk, v = u Wv [KVH, d];
             softmax(causal(d^(-1/2) q k)) v, query head i on KV head
             i // (H / KVH); out = (attn * sigmoid(z)) Wo
    MoE(v):  s = sigmoid(v Wr);  e_k = top-k of s + bias;
             g_k = scaling * s_{e_k} / sum_k s_{e_k}
             sum_{k: e_k in [first, first + count)} g_k SwiGLU_{e_k}(v)
    Shared(v):  (silu(v Wg) * (v Wu)) Wd, always on

The weights' tree holds a KDA layer's three projections as one matrix
``w_qkv`` ``[q | k | v]`` and its three convolutions as one ``conv_w`` of
three times the channels: a relabelling, split here. The share: the routed
experts ``[first, first + count)`` are held here; what the experts held
elsewhere would add is left out, as in the program, and the shared expert is
computed where the token lives. What the model's ``config.json`` does not
say stands under ``assumed`` in the configuration file.

``ops`` supplies every product (``benchmarks.lib.lowprec``): those with a
weight, the attention's two and the delta rule's three with the state (the
read ``S'^T k^``, the rank-one write, the read ``S_t^T q^``), so the control
runs these same lines in a lower precision; the decay and the state that is
carried are float32 in both. A layer is a function of its own weights alone
(``layer``), so a caller whose weights do not fit at once pushes every
sequence through one layer before it draws the next."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
KDA, ATTENTION = "kda", "attention"
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Dims:
    layer_types: Tuple[str, ...]
    heads: int                  # attention: query heads
    kv_heads: int
    head: int                   # attention: head size
    kda_heads: int              # H
    kda_head: int               # d
    conv: int                   # K
    n_routed: int               # experts the router knows, all chips'
    top_k: int
    scaling: float
    first: int                  # the share held here: [first, first + count)
    count: int
    eps: float
    beta_scale: float = 2.0     # kda_allow_neg_eigval: b in (0, 2)


def layer_types_of(config: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple(ATTENTION if i in config["gqa_layers"] else KDA
                 for i in range(config["num_hidden_layers"]))


def dims_of(config: Dict[str, Any], first: int = 0) -> Dims:
    """From a configuration file's keys (the model's public ``config.json``
    names). ``n_routed_experts`` counts the experts held here where the file
    is cut; the router keeps the published count."""
    published = config.get("published", {})
    linear = config["linear_attn_config"]
    if config["use_rope"] or not config["use_gqa_gate"] \
            or config["kda_use_full_proj"]:
        raise ValueError("written down here: no rotary, a gated attention "
                         "layer, low-rank KDA gates")
    return Dims(
        layer_types=layer_types_of(config),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head=config["head_dim"],
        kda_heads=linear["num_heads"], kda_head=linear["head_dim"],
        conv=linear["short_conv_kernel_size"],
        n_routed=published.get("n_routed_experts",
                               config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        scaling=float(config["routed_scaling_factor"]), first=first,
        count=config["n_routed_experts"], eps=float(config["rms_norm_eps"]),
        beta_scale=2.0 if config["kda_allow_neg_eigval"] else 1.0)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def delta_rule(ops, q, k, v, a, b):
    """The gated delta rule over one sequence, token by token from a zero
    state: q, k (normalised), v, a (log-decay) ``[S, H, d]``, b ``[S, H]``.
    Returns o ``[S, H, d]``. Its three products with the state are ``ops``'
    like every other; the state itself is carried in float32."""
    def one(s, row):
        q_t, k_t, v_t, a_t, b_t = row
        s = jnp.exp(a_t)[:, :, None] * s                    # [H, d(k), d(v)]
        u = b_t[:, None] * (v_t - ops.einsum("hkv,hk->hv", s, k_t))
        s = s + ops.einsum("hk,hv->hkv", k_t, u)
        return s, ops.einsum("hkv,hk->hv", s, q_t)

    zero = jnp.zeros(k.shape[1:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(one, zero, (q, k, v, a, b))
    return o


def short_conv(x, w):
    """silu of the causal depthwise convolution of x ``[S, C]`` with w ``[K,
    C]`` (``w[K - 1]`` on the row itself), zeros before the first row."""
    s, taps = x.shape[0], w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps)))


def kda(ops, dims: Dims, u, mp):
    """One KDA layer's mixer on one sequence's normed rows u [S, D]."""
    s, h, d = u.shape[0], dims.kda_heads, dims.kda_head
    w_q, w_k, w_v = jnp.split(mp["w_qkv"], 3, axis=-1)
    c_q, c_k, c_v = jnp.split(mp["conv_w"], 3, axis=-1)
    q, k, v = (
        short_conv(ops.einsum("sd,de->se", u, w), c).reshape(s, h, d)
        for w, c in ((w_q, c_q), (w_k, c_k), (w_v, c_v)))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = ops.einsum("sr,re->se", ops.einsum("sd,dr->sr", u, mp["w_f1"]),
                   mp["w_f2"])
    a = -jnp.exp(mp["A_log"])[None, :, None] * jax.nn.softplus(
        (f + mp["dt_bias"]).reshape(s, h, d))
    b = dims.beta_scale * jax.nn.sigmoid(
        ops.einsum("sd,dh->sh", u, mp["w_b"]))
    o = delta_rule(ops, q, k, v, a, b)
    gate = ops.einsum("sr,re->se", ops.einsum("sd,dr->sr", u, mp["w_g1"]),
                      mp["w_g2"])
    y = rmsnorm(o, mp["o_norm"], dims.eps).reshape(s, h * d) \
        * jax.nn.sigmoid(gate)
    return ops.einsum("se,ed->sd", y, mp["w_o"])


def attention(ops, dims: Dims, u, ap):
    """The gated grouped-query attention mixer on one sequence's rows u
    [S, D]."""
    s = u.shape[0]
    group = dims.heads // dims.kv_heads
    q = ops.einsum("sd,da->sa", u, ap["wq"]).reshape(s, dims.heads, -1)
    z = ops.einsum("sd,da->sa", u, ap["wz"])
    k = ops.einsum("sd,da->sa", u, ap["wk"]).reshape(s, dims.kv_heads, -1)
    v = ops.einsum("sd,da->sa", u, ap["wv"]).reshape(s, dims.kv_heads, -1)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = ops.einsum("qhd,khd->hqk", q, k) * dims.head ** -0.5
    pos = jnp.arange(s)
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    o = ops.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return ops.einsum("sa,ad->sd", o.reshape(s, -1) * jax.nn.sigmoid(z),
                      ap["wo"])


def swiglu(ops, u, w_gate, w_up, w_down):
    a = jax.nn.silu(ops.einsum("sd,df->sf", u, w_gate)) \
        * ops.einsum("sd,df->sf", u, w_up)
    return ops.einsum("sf,fd->sd", a, w_down)


def route(ops, dims: Dims, u, router, bias):
    """(chosen [S, k] expert ids over the whole router, g [S, k] summing to
    ``scaling`` a row)."""
    s = jax.nn.sigmoid(ops.einsum("sd,de->se", u, router))
    _, chosen = jax.lax.top_k(s + bias, dims.top_k)
    g = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, dims.scaling * g / jnp.sum(g, axis=-1, keepdims=True)


def moe(ops, dims: Dims, u, ep, shared: bool = True):
    """The expert block's output for the share ``[first, first + count)``:
    its routed experts' terms and (``shared``) the shared expert; the absent
    routed experts' terms are left out."""
    chosen, g = route(ops, dims, u, ep["router"], ep["router_bias"])

    def one(j_w):
        j, w_gate, w_up, w_down = j_w
        g_e = jnp.sum(jnp.where(chosen == dims.first + j, g, 0.0), axis=-1)
        return g_e[:, None] * swiglu(ops, u, w_gate, w_up, w_down)

    s = jnp.sum(jax.lax.map(one, (jnp.arange(dims.count), ep["w_gate"],
                                  ep["w_up"], ep["w_down"])), axis=0)
    if shared:
        sp = ep["shared"]
        s = s + swiglu(ops, u, sp["w_gate"], sp["w_up"], sp["w_down"])
    return s


def layer(ops, dims: Dims, kind: str, h, mixer_p, expert_p):
    """One layer of ``kind`` on one sequence: h [S, D]; its mixer's weights
    and its expert block's."""
    mix = kda if kind == KDA else attention
    h = h + mix(ops, dims, rmsnorm(h, mixer_p["norm"], dims.eps), mixer_p)
    return h + moe(ops, dims, rmsnorm(h, expert_p["norm"], dims.eps),
                   expert_p)


def embed(dims: Dims, embedding, tokens):
    return embedding[tokens]


def head_logits(ops, dims: Dims, h, final_norm, head):
    """Logits [R, V] of rows h [R, D] of the residual stream."""
    return ops.einsum("sd,vd->sv", rmsnorm(h, final_norm, dims.eps), head)


def logits(ops, dims: Dims, params: Params, tokens, rows) -> jax.Array:
    """Logits [len(rows), V] of one sequence at the positions ``rows``;
    ``params`` as the program's tree: the layers of one kind stacked, the
    expert blocks of all layers stacked."""
    h = embed(dims, params["embed"], tokens)
    seen = {KDA: 0, ATTENTION: 0}
    for l, kind in enumerate(dims.layer_types):
        i = seen[kind]
        seen[kind] += 1
        h = layer(ops, dims, kind, h,
                  jax.tree.map(lambda a: a[i], params["layers"][kind]),
                  jax.tree.map(lambda a: a[l], params["layers"]["moe"]))
    return head_logits(ops, dims, h[rows], params["final_norm"],
                       params["head"])
