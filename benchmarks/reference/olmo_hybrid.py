"""Plain reference of the gated DeltaNet hybrid stack (Olmo Hybrid's layer):
gated DeltaNet layers (the gated delta rule of arXiv:2412.06464 with one
decay a head, as the public ``fla`` GatedDeltaNet layer computes it) with a
full multi-head attention layer among every four, a dense SwiGLU after each,
every sublayer normed after it (Olmo 2's order). float32, one whole sequence
at a time: no cache, no slot state, no kernels; the delta rule TOKEN BY TOKEN
(a ``lax.scan`` of the equations, never the chunked form), the full causal
attention. It imports nothing of the program. Equations, ``N`` = RMSNorm
(eps from the configuration, scale only), no norm before a sublayer::

    h0 = E[token]
    h = h + N_mix(Mixer(h));  h = h + N_ffn(SwiGLU(h))    Mixer by layer_types
    logits = N(h) W_head                                  the untied head

    GDN(x):  q = silu(conv(x W_q)), k = silu(conv(x W_k))   each [H, d_k]
             v = silu(conv(x W_v))                          [H, d_v]
                     (depthwise, causal, width K, zeros before t = 0, no bias)
             q^ = q / sqrt(|q|^2 + 1e-6) * d_k^(-1/2)
             k^ = k / sqrt(|k|^2 + 1e-6)
             g_t = -exp(A_log) * softplus(x W_a + dt_bias)  [H]
             b_t = 2 sigmoid(x W_b)                         [H]
             S_t = e^g S_{t-1} + b k^ (v - e^g S_{t-1}^T k^)^T;  S_{-1} = 0
             o_t = S_t^T q^;  out = (N_w(o_t) per head * silu(x W_g)) W_o
    MHA(x):  q = N_q(x W_q), k = N_k(x W_k) over the whole width, v = x W_v;
             softmax(causal(d^(-1/2) q k)) v, H heads; out = attn W_o
    SwiGLU(x) = (silu(x W_gate) * (x W_up)) W_down

The weights' tree holds a GDN layer's three projections as one matrix
``w_qkv`` ``[q | k | v]`` and its three convolutions as one ``conv_w``: a
relabelling, split here. What the model's ``config.json`` does not say stands
under ``assumed`` in the configuration file.

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
GDN, ATTENTION = "gdn", "attention"
L2_EPS = 1e-6
KINDS = {"linear_attention": GDN, "full_attention": ATTENTION}


@dataclasses.dataclass(frozen=True)
class Dims:
    layer_types: Tuple[str, ...]
    heads: int                  # attention: query heads
    kv_heads: int
    head: int                   # attention: head size
    gdn_heads: int              # H
    d_key: int                  # d_k
    d_value: int                # d_v
    conv: int                   # K
    eps: float
    beta_scale: float = 2.0     # linear_allow_neg_eigval: b in (0, 2)


def layer_types_of(config: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple(KINDS[t] for t in config["layer_types"])


def dims_of(config: Dict[str, Any]) -> Dims:
    """From a configuration file's keys (the model's public ``config.json``
    names). Refuses the readings not written down here."""
    if config["rope_parameters"].get("rope_theta") is not None \
            or config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["linear_num_key_heads"] \
            != config["linear_num_value_heads"]:
        raise ValueError("written down here: no rotary, no biases, silu, "
                         "as many key heads as value heads")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    return Dims(
        layer_types=layer_types_of(config),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head=config["hidden_size"] // config["num_attention_heads"],
        gdn_heads=config["linear_num_key_heads"],
        d_key=config["linear_key_head_dim"],
        d_value=config["linear_value_head_dim"],
        conv=config["linear_conv_kernel_dim"],
        eps=float(config["rms_norm_eps"]),
        beta_scale=2.0 if config["linear_allow_neg_eigval"] else 1.0)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def delta_rule(ops, q, k, v, g, b):
    """The gated delta rule over one sequence, token by token from a zero
    state: q, k (normalised) ``[S, H, d_k]``, v ``[S, H, d_v]``, g (log-decay)
    and b ``[S, H]``. Returns o ``[S, H, d_v]``. Its three products with the
    state are ``ops``' like every other; the state itself is carried in
    float32."""
    def one(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[:, None, None] * s                 # [H, d_k, d_v]
        u = b_t[:, None] * (v_t - ops.einsum("hkv,hk->hv", s, k_t))
        s = s + ops.einsum("hk,hv->hkv", k_t, u)
        return s, ops.einsum("hkv,hk->hv", s, q_t)

    zero = jnp.zeros(k.shape[1:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(one, zero, (q, k, v, g, b))
    return o


def short_conv(x, w):
    """silu of the causal depthwise convolution of x ``[S, C]`` with w ``[K,
    C]`` (``w[K - 1]`` on the row itself), zeros before the first row."""
    s, taps = x.shape[0], w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps)))


def gdn(ops, dims: Dims, x, mp):
    """One gated DeltaNet layer's mixer on one sequence's rows x [S, D]."""
    s, h, dk, dv = x.shape[0], dims.gdn_heads, dims.d_key, dims.d_value
    split = (h * dk, 2 * h * dk)
    w_q, w_k, w_v = jnp.split(mp["w_qkv"], split, axis=-1)
    c_q, c_k, c_v = jnp.split(mp["conv_w"], split, axis=-1)
    q, k, v = (short_conv(ops.einsum("sd,de->se", x, w), c)
               for w, c in ((w_q, c_q), (w_k, c_k), (w_v, c_v)))
    q, k, v = q.reshape(s, h, dk), k.reshape(s, h, dk), v.reshape(s, h, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    g = -jnp.exp(mp["A_log"])[None, :] * jax.nn.softplus(
        ops.einsum("sd,dh->sh", x, mp["w_a"]) + mp["dt_bias"])
    b = dims.beta_scale * jax.nn.sigmoid(ops.einsum("sd,dh->sh", x,
                                                    mp["w_b"]))
    o = delta_rule(ops, q, k, v, g, b)
    y = rmsnorm(o, mp["o_norm"], dims.eps).reshape(s, h * dv) \
        * jax.nn.silu(ops.einsum("sd,de->se", x, mp["w_g"]))
    return ops.einsum("se,ed->sd", y, mp["w_o"])


def attention(ops, dims: Dims, x, ap):
    """The multi-head attention mixer with q/k norms on one sequence's rows
    x [S, D]."""
    s = x.shape[0]
    group = dims.heads // dims.kv_heads
    q = rmsnorm(ops.einsum("sd,da->sa", x, ap["wq"]), ap["q_norm"],
                dims.eps).reshape(s, dims.heads, -1)
    k = rmsnorm(ops.einsum("sd,da->sa", x, ap["wk"]), ap["k_norm"],
                dims.eps).reshape(s, dims.kv_heads, -1)
    v = ops.einsum("sd,da->sa", x, ap["wv"]).reshape(s, dims.kv_heads, -1)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = ops.einsum("qhd,khd->hqk", q, k) * dims.head ** -0.5
    pos = jnp.arange(s)
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    o = ops.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return ops.einsum("sa,ad->sd", o.reshape(s, -1), ap["wo"])


def swiglu(ops, x, fp):
    a = jax.nn.silu(ops.einsum("sd,df->sf", x, fp["w_gate"])) \
        * ops.einsum("sd,df->sf", x, fp["w_up"])
    return ops.einsum("sf,fd->sd", a, fp["w_down"])


def layer(ops, dims: Dims, kind: str, h, mixer_p, mlp_p):
    """One layer of ``kind`` on one sequence: h [S, D]; its mixer's weights
    (``norm`` the norm after it) and its SwiGLU's (``norm`` likewise)."""
    mix = gdn if kind == GDN else attention
    h = h + rmsnorm(mix(ops, dims, h, mixer_p), mixer_p["norm"], dims.eps)
    return h + rmsnorm(swiglu(ops, h, mlp_p), mlp_p["norm"], dims.eps)


def head_logits(ops, dims: Dims, h, final_norm, head):
    """Logits [R, V] of rows h [R, D] of the residual stream."""
    return ops.einsum("sd,vd->sv", rmsnorm(h, final_norm, dims.eps), head)


def logits(ops, dims: Dims, params: Params, tokens, rows) -> jax.Array:
    """Logits [len(rows), V] of one sequence at the positions ``rows``;
    ``params`` as the program's tree: the layers of one kind stacked, the
    SwiGLUs of all layers stacked."""
    h = params["embed"][tokens]
    seen = {GDN: 0, ATTENTION: 0}
    for l, kind in enumerate(dims.layer_types):
        i = seen[kind]
        seen[kind] += 1
        h = layer(ops, dims, kind, h,
                  jax.tree.map(lambda a: a[i], params["layers"][kind]),
                  jax.tree.map(lambda a: a[l], params["layers"]["mlp"]))
    return head_logits(ops, dims, h[rows], params["final_norm"],
                       params["head"])
