"""Operations and bytes the delta-rule hybrid stack (Solar Open 2's layer)
REQUIRES of the chip that holds one share of it, from the configuration's
sizes (the keys of the model's public ``config.json``; ``n_routed_experts``
counts the experts held here, the router keeps the published width).

Operations: 2 per multiply-add of every product with a weight; for a KDA
layer the recurrence's three products with the state a token (the read
``S'^T k^``, the rank-one write and the read ``S_t^T q^``: 2 H d d each,
``6 H d d`` in all); for an attention layer the scores and the weighted sum
over the keys a token may see. A routed expert's products count only for the
tokens routed to it, and only the held experts': what this chip must do,
whatever a masked product does besides.

Bytes of a decode step: every weight outside the routed experts once,
whatever the batch; the head's slice; the embedding rows of the tokens; the
three matrices of each held expert that got at least one row, in each layer;
the K and V rows of the live context; and the live slots' delta-rule state
and convolution tails READ AND WRITTEN once each, at the state's dtype. A
count from shapes and counters, the same whatever implements the step."""

from __future__ import annotations

from typing import Any, Dict

ROUTER_BYTES = 4        # the router is served in float32
STATE_BYTES = 4         # the delta-rule state and the conv tails: float32


def router_width(config: Dict[str, Any]) -> int:
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each kind are held."""
    n = config["num_hidden_layers"]
    gqa = sum(1 for i in config["gqa_layers"] if i < n)
    return {"kda": n - gqa, "attention": gqa, "all": n}


def parameters(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts: one KDA mixer's products (and its small float32
    leaves apart), one attention mixer, the router (its selection bias
    apart), one routed expert, the shared expert, the head's slice (the
    embedding's is as large)."""
    d = config["hidden_size"]
    linear = config["linear_attn_config"]
    h, w = linear["num_heads"], linear["num_heads"] * linear["head_dim"]
    hq = config["num_attention_heads"] * config["head_dim"]
    hkv = config["num_key_value_heads"] * config["head_dim"]
    f = config["moe_intermediate_size"]
    rank = linear["head_dim"]       # of the decay's and the output's gate
    return {"kda": float(3 * d * w + w * d + 2 * (d * rank + rank * w)
                         + d * h),
            "kda_small": float(3 * w * linear["short_conv_kernel_size"]
                               + w + h + linear["head_dim"]),
            "attention": float(2 * d * hq + 2 * d * hkv + hq * d),
            "router": float(d * router_width(config)),
            "router_small": float(router_width(config)),
            "expert": 3.0 * d * f,
            "shared": 3.0 * d * f * config["n_shared_experts"],
            "head": float(d * config["vocab_size"])}


def slot_state_numbers(config: Dict[str, Any]) -> float:
    """Numbers one slot keeps in one KDA layer: the state ``[H, d, d]`` and
    the last ``K - 1`` inputs of the three convolutions."""
    linear = config["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]
    return float(h * d * d
                 + (linear["short_conv_kernel_size"] - 1) * 3 * h * d)


def forward_flops(config: Dict[str, Any], new_tokens: int,
                  context_before: int = 0, logit_rows: int = None) -> float:
    """Forward operations to push ``new_tokens`` tokens of one sequence
    through every layer held here, the first of them at position
    ``context_before``, WITHOUT the routed experts' products (they depend on
    the routing: ``expert_flops`` an assignment). ``logit_rows``: how many
    of the tokens need logits (all by default)."""
    p, layers = parameters(config), layer_counts(config)
    n, c = new_tokens, context_before
    rows = n if logit_rows is None else logit_rows
    keys_seen = n * c + n * (n + 1) // 2
    per_key = 2.0 * config["num_attention_heads"] * 2 * config["head_dim"]
    linear = config["linear_attn_config"]
    recurrence = 6.0 * linear["num_heads"] * linear["head_dim"] ** 2
    return (layers["kda"] * (2.0 * p["kda"] + recurrence) * n
            + layers["attention"] * (2.0 * p["attention"] * n
                                     + per_key * keys_seen)
            + layers["all"] * 2.0 * (p["router"] + p["shared"]) * n
            + 2.0 * p["head"] * rows)


def expert_flops(config: Dict[str, Any]) -> float:
    """One token through one routed expert."""
    return 2.0 * parameters(config)["expert"]


def decode_step_bytes(config: Dict[str, Any], rows: int,
                      experts_with_rows: float, cached_tokens: float,
                      live_slots: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step over ``rows`` slots must move:
    ``experts_with_rows`` held experts that got a row, summed over the
    layers; ``cached_tokens`` keys over all slots (the new ones included);
    ``live_slots`` slots whose state the step advances (read and written
    once each)."""
    p, layers = parameters(config), layer_counts(config)
    outside = (layers["kda"] * (p["kda"] * bytes_per_el + p["kda_small"] * 4)
               + layers["attention"] * p["attention"] * bytes_per_el
               + layers["all"] * (p["shared"] * bytes_per_el
                                  + (p["router"] + p["router_small"])
                                  * ROUTER_BYTES))
    kv = (layers["attention"] * cached_tokens * 2
          * config["num_key_value_heads"] * config["head_dim"]
          * bytes_per_el)
    state = (2 * layers["kda"] * live_slots * slot_state_numbers(config)
             * STATE_BYTES)
    return (outside + p["head"] * bytes_per_el
            + rows * config["hidden_size"] * bytes_per_el
            + experts_with_rows * p["expert"] * bytes_per_el + kv + state)
