"""Operations and bytes the hybrid state-space / attention stack REQUIRES of
the chip that holds one share of it, from the configuration's sizes (the keys
of the model's public ``config.json``; ``num_local_experts`` counts the
experts held here, the router keeps the published width).

Operations: 2 per multiply-add of every product with a weight; for a Mamba
layer the recurrence's two products with the state a token (the update ``x
(x) B`` and the read ``S C``: 2 H P N each); for an attention layer the scores
and the weighted sum over the keys a token may see. A routed expert's
products count only for the tokens routed to it, and only the held experts':
what this chip must do, whatever a masked product does besides.

Bytes of a decode step: every weight outside the routed experts once,
whatever the batch; the head's slice (the tied embedding); the embedding rows
of the tokens; the three matrices of each held expert that got at least one
row, in each layer; the K and V rows of the live context; and the live slots'
recurrent state and convolution tail READ AND WRITTEN once each, at the
state's dtype. A count from shapes and counters, the same whatever
implements the step."""

from __future__ import annotations

from typing import Any, Dict

ROUTER_BYTES = 4        # the router is served in float32
STATE_BYTES = 4         # the recurrent state and the conv tail: float32
MAMBA, ATTENTION = "mamba", "attention"


def router_width(config: Dict[str, Any]) -> int:
    return config.get("published", {}).get(
        "num_local_experts", config["num_local_experts"])


def parameters(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts: one Mamba mixer's products (and its small float32
    leaves apart), one attention mixer, the router, one routed expert, the
    shared expert, the head's slice."""
    d, h = config["hidden_size"], config["mamba_n_heads"]
    di = h * config["mamba_d_head"]
    cd = di + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    dh = d // config["num_attention_heads"]
    hq = config["num_attention_heads"] * dh
    hkv = config["num_key_value_heads"] * dh
    return {"mamba": float(d * (di + cd + h) + di * d),
            "mamba_small": float(config["mamba_d_conv"] * cd + cd + 3 * h
                                 + di),
            "attention": float(2 * d * hq + 2 * d * hkv),
            "router": float(d * router_width(config)),
            "expert": 3.0 * d * config["intermediate_size"],
            "shared": 3.0 * d * config["shared_intermediate_size"],
            "head": float(d * config["vocab_size"])}


def slot_state_numbers(config: Dict[str, Any]) -> float:
    """Numbers one slot keeps in one Mamba layer: the state ``[H, P, N]``
    and the last ``K - 1`` inputs of the convolution."""
    h, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    cd = h * p + 2 * config["mamba_n_groups"] * n
    return float(h * p * n + (config["mamba_d_conv"] - 1) * cd)


def forward_flops(config: Dict[str, Any], new_tokens: int,
                  context_before: int = 0, logit_rows: int = None) -> float:
    """Forward operations to push ``new_tokens`` tokens of one sequence
    through every layer held here, the first of them at position
    ``context_before``, WITHOUT the routed experts' products (they depend on
    the routing: ``expert_flops`` an assignment). ``logit_rows``: how many
    of the tokens need logits (all by default)."""
    p = parameters(config)
    kinds = config["layer_types"]
    n, c = new_tokens, context_before
    rows = n if logit_rows is None else logit_rows
    keys_seen = n * c + n * (n + 1) // 2
    dh = config["hidden_size"] // config["num_attention_heads"]
    per_key = 2.0 * config["num_attention_heads"] * 2 * dh  # q k and p v
    recurrence = 2.0 * 2 * (config["mamba_n_heads"] * config["mamba_d_head"]
                            * config["mamba_d_state"])
    return (kinds.count(MAMBA) * (2.0 * p["mamba"] + recurrence) * n
            + kinds.count(ATTENTION) * (2.0 * p["attention"] * n
                                        + per_key * keys_seen)
            + len(kinds) * 2.0 * (p["router"] + p["shared"]) * n
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
    p = parameters(config)
    kinds = config["layer_types"]
    n_mamba, n_attention = kinds.count(MAMBA), kinds.count(ATTENTION)
    outside = (n_mamba * (p["mamba"] * bytes_per_el + p["mamba_small"] * 4)
               + n_attention * p["attention"] * bytes_per_el
               + len(kinds) * (p["shared"] * bytes_per_el
                               + p["router"] * ROUTER_BYTES))
    dh = config["hidden_size"] // config["num_attention_heads"]
    kv = (n_attention * cached_tokens * 2 * config["num_key_value_heads"]
          * dh * bytes_per_el)
    state = (2 * n_mamba * live_slots * slot_state_numbers(config)
             * STATE_BYTES)
    return (outside + p["head"] * bytes_per_el
            + rows * config["hidden_size"] * bytes_per_el
            + experts_with_rows * p["expert"] * bytes_per_el + kv + state)
