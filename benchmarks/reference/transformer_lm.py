"""Plain reference of the repo's dense transformer block, as configured for
the Pythia-410M-sized LM: float32, no kernels, no cache, no batching tricks.
It imports nothing of the program. Equations (each a departure from GPT-NeoX
that the configuration file lists under ``assumed``):

    x   = embed[tokens]
    per layer:  h = rmsnorm(x) * attn_norm
                q, k, v = h wq, h wk, h wv     (heads of head_dim)
                q, k = rope(q), rope(k)        (interleaved pairs, base 1e4,
                                                the whole head)
                x = x + softmax(causal(q k^T / sqrt(head_dim))) v  wo
                h = rmsnorm(x) * mlp_norm
                x = x + gelu_tanh(h w_in) w_out
    logits = (rmsnorm(x) * final_norm) head
    loss   = mean over tokens of cross entropy

``ops`` supplies the products (``benchmarks.lib.lowprec``), so the control
runs these same lines in a lower precision."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
EPS = 1e-6


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * scale


def rope(x, pos):
    """x [S, H, D], pos [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _layer(ops, head_dim: int, x, lp):
    """One block on one sequence: x [S, D]."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = rmsnorm(x, lp["attn_norm"])
    q, k, v = (ops.einsum("sd,da->sa", h, lp[w]).reshape(s, -1, head_dim)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, pos), rope(k, pos)
    scores = ops.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = ops.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
    x = x + ops.einsum("sa,ad->sd", o, lp["wo"])
    h = rmsnorm(x, lp["mlp_norm"])
    u = jax.nn.gelu(ops.einsum("sd,df->sf", h, lp["w_in"]), approximate=True)
    return x + ops.einsum("sf,fd->sd", u, lp["w_out"])


def hidden(ops, head_dim: int, params: Params, tokens) -> jax.Array:
    """Final normed hidden states of ONE sequence: tokens [S] -> [S, D].
    Each layer is recomputed in the backward pass, so what is kept is one
    [S, D] per layer."""
    x = params["embed"][tokens]

    def body(x, lp):
        return jax.checkpoint(
            lambda x, lp: _layer(ops, head_dim, x, lp))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"])


def logits(ops, head_dim: int, params: Params, tokens, rows) -> jax.Array:
    """Logits [len(rows), V] of one sequence at the positions ``rows``."""
    x = hidden(ops, head_dim, params, tokens)[rows]
    return ops.einsum("sd,dv->sv", x, params["head"])


def loss_sum(ops, head_dim: int, params: Params, tokens, labels
             ):
    """Sum (not mean) of the cross entropy over a block of sequences
    [R, S], and (the number of tokens, no further state): the harness adds
    the blocks up."""
    def one(tok, lab):
        lg = logits(ops, head_dim, params, tok, jnp.arange(tok.shape[0]))
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))

    total = jnp.sum(jax.lax.map(lambda tl: one(*tl), (tokens, labels)))
    return total, (jnp.float32(tokens.size), None)
