"""Operations and bytes the DeepSeek-V3 stack (Kimi K2's) REQUIRES of the chip
that holds one share of it, from the configuration's sizes (the keys of the
model's public ``config.json``; ``n_routed_experts`` counts the experts held
here, the router keeps the published width).

Operations: 2 per multiply-add of every product with a weight and, for
attention, the scores and the weighted sum over the keys a token may see
(causal, the live context only), in the cheaper of the two forms of latent
attention: keys and values expanded from the latent row (``nope + rope`` and
``v`` numbers a key and head, where the absorbed form pays ``kv_lora_rank +
rope`` and ``kv_lora_rank``), the expansion itself (``kv_b``) counted for
the new rows only, as a product with a weight. A routed expert's products
count only for the tokens routed to it, and only the held experts': what
this chip must do, whatever a masked product does besides.

Bytes of a decode step: every weight outside the routed experts once,
whatever the batch; the head's slice; the embedding rows of the tokens; the
three matrices of each held expert that got at least one row, in each
layer; the latent rows of the live context (``kv_lora_rank + rope`` numbers,
not the lanes a tile pads them to), once each layer."""

from __future__ import annotations

from typing import Any, Dict

ROUTER_BYTES = 4        # the router is served in float32


def router_width(config: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file is cut."""
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Layers held here: all, the leading dense ones, the expert ones."""
    n = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], n)
    return {"all": n, "dense": dense, "moe": n - dense}


def parameters(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts: one attention block, the dense SwiGLU, the router,
    the shared expert, one routed expert, the head's slice."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    fe = config["moe_intermediate_size"]
    mla = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
           + rkv * h * (dn + dv) + h * dv * d)
    return {"mla": float(mla),
            "dense": 3.0 * d * config["intermediate_size"],
            "router": float(d * router_width(config)),
            "shared": 3.0 * d * fe * config["n_shared_experts"],
            "expert": 3.0 * d * fe,
            "head": float(d * config["vocab_size"])}


def outside_experts(config: Dict[str, Any]) -> float:
    """Every weight a token passes through but the routed experts'."""
    p, n = parameters(config), layer_counts(config)
    return (n["all"] * p["mla"] + n["dense"] * p["dense"]
            + n["moe"] * (p["router"] + p["shared"]))


def forward_flops(config: Dict[str, Any], new_tokens: int,
                  context_before: int = 0, logit_rows: int = None) -> float:
    """Forward operations to push ``new_tokens`` tokens of one sequence
    through every layer held here, the first of them at position
    ``context_before``, WITHOUT the routed experts' products (they depend on
    the routing: ``expert_flops`` an assignment). ``logit_rows``: how many
    of the tokens need logits (all by default)."""
    n, c = new_tokens, context_before
    rows = n if logit_rows is None else logit_rows
    keys_seen = n * c + n * (n + 1) // 2
    per_key = 2.0 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
    return (2.0 * outside_experts(config) * n
            + layer_counts(config)["all"] * per_key * keys_seen
            + 2.0 * parameters(config)["head"] * rows)


def expert_flops(config: Dict[str, Any]) -> float:
    """One token through one routed expert."""
    return 2.0 * parameters(config)["expert"]


def decode_step_bytes(config: Dict[str, Any], rows: int,
                      experts_with_rows: float, cached_tokens: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step over ``rows`` slots must read:
    ``experts_with_rows`` held experts that got a row, summed over the
    layers; ``cached_tokens`` keys over all slots (the new ones included)."""
    p = parameters(config)
    router = layer_counts(config)["moe"] * p["router"]
    latent = (layer_counts(config)["all"] * cached_tokens * bytes_per_el
              * (config["kv_lora_rank"] + config["qk_rope_head_dim"]))
    return ((outside_experts(config) - router + p["head"]) * bytes_per_el
            + router * ROUTER_BYTES
            + rows * config["hidden_size"] * bytes_per_el
            + experts_with_rows * p["expert"] * bytes_per_el + latent)
