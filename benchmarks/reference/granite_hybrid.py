"""Plain reference of the hybrid stack (Granite 4.0-H's layer): Mamba-2
state-space layers with a grouped-query attention layer without positional
embedding among every few, each followed by routed experts plus a shared
expert. float32, one whole sequence at a time: no cache, no slot state, no
kernels; the state-space recurrence TOKEN BY TOKEN (never the chunked form),
a plain loop over the held experts, the full causal attention. It imports
nothing of the program. Equations, ``N`` = RMSNorm (eps from the
configuration, scale only), ``r`` = ``residual_multiplier``::

    h0 = embedding_multiplier * E[token]
    u = N(h);  h = h + r * Mixer(u)              Mixer by ``layer_types``
    v = N(h);  h = h + r * (MoE(v) + Shared(v))
    logits = N(h) E^T / logits_scaling            E: the tied embedding's slice

    Mamba2(u):  [z | xBC | dt] = u W_in           d_inner | d_inner + 2 N | H
                xBC_t = silu(b + sum_{j<K} w[j] * xBC_{t-K+1+j})   zeros before t = 0
                [x | B | C] = xBC;  x as [H, P]
                D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
                S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t;  S_{-1} = 0
                y_t[h] = S_t[h] C_t + D[h] x_t[h]
                out = N_w(y * silu(z)) W_out
    Attention(u): q = u Wq [H, d], k = u Wk, v = u Wv [KVH, d]; no rotary
                softmax(causal(attention_multiplier * q k)) v, query head i
                on KV head i // (H / KVH); concat Wo
    MoE(v):     l = v Wr;  (l_k, e_k) = top-k of l;  g = softmax(l_k)
                sum_{k: e_k in [first, first + count)} g_k SwiGLU_{e_k}(v)
    Shared(v):  (silu(v Wg) * (v Wu)) Wd, always on

The share: the routed experts ``[first, first + count)`` are held here; what
the experts held elsewhere would add is left out, as in the program, and the
shared expert is computed where the token lives. What the model's
``config.json`` does not say stands under ``assumed`` in the configuration
file.

``ops`` supplies every product (``benchmarks.lib.lowprec``): those with a
weight, the attention's two and the recurrence's two (the update's outer
product and ``S C``), so the control runs these same lines in a lower
precision; the decay and the state that is carried are float32 in both. A
layer is a function of its own weights alone (``layer``), so a caller whose
weights do not fit at once pushes every sequence through one layer before it
draws the next."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class Dims:
    layer_types: Tuple[str, ...]
    heads: int                  # attention: query heads
    kv_heads: int
    attention_multiplier: float
    ssm_heads: int              # H
    ssm_head: int               # P
    ssm_state: int              # N
    conv: int                   # K
    n_routed: int               # experts the router knows, all chips'
    top_k: int
    first: int                  # the share held here: [first, first + count)
    count: int
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    eps: float


def dims_of(config: Dict[str, Any], first: int = 0) -> Dims:
    """From a configuration file's keys (the model's public ``config.json``
    names). ``num_local_experts`` counts the experts held here where the file
    is cut; the router keeps the published count."""
    published = config.get("published", {})
    if config["mamba_n_groups"] != 1:
        raise ValueError("one group of B and C is written down here")
    return Dims(
        layer_types=tuple(config["layer_types"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        attention_multiplier=float(config["attention_multiplier"]),
        ssm_heads=config["mamba_n_heads"], ssm_head=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"], conv=config["mamba_d_conv"],
        n_routed=published.get("num_local_experts",
                               config["num_local_experts"]),
        top_k=config["num_experts_per_tok"], first=first,
        count=config["num_local_experts"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        eps=float(config["rms_norm_eps"]))


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def recurrence(ops, x, step, a, b, c, d_skip):
    """The state-space recurrence over one sequence, token by token from a
    zero state: x ``[S, H, P]``, step ``[S, H]`` (after softplus), a ``[H]``,
    b and c ``[S, N]``, d_skip ``[H]``. Returns y ``[S, H, P]``. Its two
    products, the update ``(step x) (x) B`` and the read ``S C``, are
    ``ops``' like every other; the state itself is carried in float32."""
    def one(s, row):
        x_t, step_t, b_t, c_t = row
        s = jnp.exp(step_t * a)[:, None, None] * s \
            + ops.einsum("hp,n->hpn", step_t[:, None] * x_t, b_t)
        return s, ops.einsum("hpn,n->hp", s, c_t) + d_skip[:, None] * x_t

    zero = jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(one, zero, (x, step, b, c))
    return y


def mamba(ops, dims: Dims, u, mp):
    """One Mamba-2 layer's mixer on one sequence's normed rows u [S, D]."""
    s = u.shape[0]
    d_inner = dims.ssm_heads * dims.ssm_head
    conv_dim = d_inner + 2 * dims.ssm_state
    zxd = ops.einsum("sd,de->se", u, mp["w_in"])
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:d_inner + conv_dim],
                  zxd[:, d_inner + conv_dim:])
    padded = jnp.concatenate(
        [jnp.zeros((dims.conv - 1, conv_dim), xbc.dtype), xbc], axis=0)
    xbc = jax.nn.silu(mp["conv_b"] + sum(
        mp["conv_w"][j] * padded[j:j + s] for j in range(dims.conv)))
    x = xbc[:, :d_inner].reshape(s, dims.ssm_heads, dims.ssm_head)
    b = xbc[:, d_inner:d_inner + dims.ssm_state]
    c = xbc[:, d_inner + dims.ssm_state:]
    y = recurrence(ops, x, jax.nn.softplus(dt + mp["dt_bias"]),
                   -jnp.exp(mp["A_log"]), b, c, mp["D"])
    g = rmsnorm(y.reshape(s, d_inner) * jax.nn.silu(z), mp["gate_norm"],
                dims.eps)
    return ops.einsum("se,ed->sd", g, mp["w_out"])


def attention(ops, dims: Dims, u, ap):
    """The grouped-query attention mixer on one sequence's rows u [S, D]."""
    s = u.shape[0]
    group = dims.heads // dims.kv_heads
    q = ops.einsum("sd,da->sa", u, ap["wq"]).reshape(s, dims.heads, -1)
    k = ops.einsum("sd,da->sa", u, ap["wk"]).reshape(s, dims.kv_heads, -1)
    v = ops.einsum("sd,da->sa", u, ap["wv"]).reshape(s, dims.kv_heads, -1)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = ops.einsum("qhd,khd->hqk", q, k) * dims.attention_multiplier
    pos = jnp.arange(s)
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    o = ops.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return ops.einsum("sa,ad->sd", o.reshape(s, -1), ap["wo"])


def swiglu(ops, u, w_gate, w_up, w_down):
    a = jax.nn.silu(ops.einsum("sd,df->sf", u, w_gate)) \
        * ops.einsum("sd,df->sf", u, w_up)
    return ops.einsum("sf,fd->sd", a, w_down)


def route(ops, dims: Dims, u, router):
    """(chosen [S, k] expert ids over the whole router, g [S, k] summing to
    1 a row)."""
    chosen_logits, chosen = jax.lax.top_k(
        ops.einsum("sd,de->se", u, router), dims.top_k)
    return chosen, jax.nn.softmax(chosen_logits, axis=-1)


def moe(ops, dims: Dims, u, ep, shared: bool = True):
    """The expert block's output for the share ``[first, first + count)``:
    its routed experts' terms and (``shared``) the shared expert; the absent
    routed experts' terms are left out."""
    chosen, g = route(ops, dims, u, ep["router"])

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
    mix = mamba if kind == MAMBA else attention
    h = h + dims.residual_multiplier * mix(
        ops, dims, rmsnorm(h, mixer_p["norm"], dims.eps), mixer_p)
    return h + dims.residual_multiplier * moe(
        ops, dims, rmsnorm(h, expert_p["norm"], dims.eps), expert_p)


def embed(dims: Dims, embedding, tokens):
    return dims.embedding_multiplier * embedding[tokens]


def head_logits(ops, dims: Dims, h, final_norm, embedding):
    """Logits [R, V] of rows h [R, D] of the residual stream."""
    return ops.einsum("sd,vd->sv", rmsnorm(h, final_norm, dims.eps),
                      embedding) / dims.logits_scaling


def logits(ops, dims: Dims, params: Params, tokens, rows) -> jax.Array:
    """Logits [len(rows), V] of one sequence at the positions ``rows``;
    ``params`` as the program's tree: the layers of one kind stacked, the
    expert blocks of all layers stacked."""
    h = embed(dims, params["embed"], tokens)
    seen = {MAMBA: 0, ATTENTION: 0}
    for l, kind in enumerate(dims.layer_types):
        i = seen[kind]
        seen[kind] += 1
        h = layer(ops, dims, kind, h,
                  jax.tree.map(lambda a: a[i], params["layers"][kind]),
                  jax.tree.map(lambda a: a[l], params["layers"]["moe"]))
    return head_logits(ops, dims, h[rows], params["final_norm"],
                       params["embed"])
