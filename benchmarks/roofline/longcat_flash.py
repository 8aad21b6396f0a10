"""Operations and bytes the shortcut-MoE latent-attention model REQUIRES of
the chip that holds one share of it, from the configuration's sizes (the keys
of the model's public ``config.json``; ``n_routed_experts`` counts the experts
held here, the router keeps the published width).

Operations: 2 per multiply-add of every product with a weight and, for
attention, the scores and the weighted sum over the keys a token may see, in
the cheaper of the two forms (keys and values expanded from the latent row:
``nope + rope`` and ``v`` numbers a key and head, where the absorbed form
pays ``kv_lora_rank + rope`` and ``kv_lora_rank``). An expert's products
count only for the tokens routed to it, and only the held experts': what
this chip must do, whatever a masked product does besides.

Bytes of a decode step: every weight outside the experts once, whatever the
batch; the head's slice; the embedding rows of the tokens; the three matrices
of each held expert that got at least one row, in each layer; the latent
rows of the live context, once each attention block."""

from __future__ import annotations

from typing import Any, Dict

ROUTER_BYTES = 4        # the router is served in float32


def router_width(config: Dict[str, Any]) -> int:
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    return routed + config["zero_expert_num"]


def parameters(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts: one attention block, one dense FFN, the router, one
    expert, one layer outside its experts, the head's slice."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    mla = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
           + rkv * h * (dn + dv) + h * dv * d)
    ffn = 3 * d * config["ffn_hidden_size"]
    router = d * router_width(config)
    return {"mla": float(mla), "ffn": float(ffn), "router": float(router),
            "expert": 3.0 * d * config["expert_ffn_hidden_size"],
            "layer_outside_experts": 2.0 * mla + 2.0 * ffn + router,
            "head": float(d * config["vocab_size"])}


def forward_flops(config: Dict[str, Any], new_tokens: int,
                  context_before: int = 0, logit_rows: int = None) -> float:
    """Forward operations to push ``new_tokens`` tokens of one sequence
    through every layer held here, the first of them at position
    ``context_before``, WITHOUT the routed experts' products (they depend on
    the routing: ``expert_flops`` an assignment). ``logit_rows``: how many
    of the tokens need logits (all by default)."""
    p = parameters(config)
    n, c = new_tokens, context_before
    rows = n if logit_rows is None else logit_rows
    keys_seen = n * c + n * (n + 1) // 2
    per_key = 2.0 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
    per_layer = (2.0 * p["layer_outside_experts"] * n
                 + 2.0 * per_key * keys_seen)       # two attention blocks
    return config["num_layers"] * per_layer + 2.0 * p["head"] * rows


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
    layers = config["num_layers"]
    outside = layers * (
        (p["layer_outside_experts"] - p["router"]) * bytes_per_el
        + p["router"] * ROUTER_BYTES)
    latent = (2 * layers * cached_tokens * bytes_per_el
              * (config["kv_lora_rank"] + config["qk_rope_head_dim"]))
    return (outside + p["head"] * bytes_per_el
            + rows * config["hidden_size"] * bytes_per_el
            + experts_with_rows * p["expert"] * bytes_per_el + latent)
