"""Operations and bytes the gated DeltaNet hybrid stack (Olmo Hybrid's layer)
REQUIRES of the chip that holds one share of it, from the configuration's
sizes (the keys of the model's public ``config.json``; ``num_hidden_layers``
and ``layer_types`` what this chip holds).

Operations: 2 per multiply-add of every product with a weight; for a GDN
layer the recurrence's three products with the state a token (the read
``S'^T k^``, the rank-one write and the read ``S_t^T q^``: ``2 H d_k d_v``
each, ``6 H d_k d_v`` in all); for an attention layer the scores and the
weighted sum over the keys a token may see; the head for the rows that need
logits. What the chunked form of the rule computes besides is no required
work.

Bytes of a decode step: every weight but the embedding once, whatever the
batch (the float32 small leaves at 4 bytes); the embedding rows of the
tokens; the K and V rows of the live context; and the live slots'
delta-rule state and convolution tails READ AND WRITTEN once each, at the
state's dtype. A count from shapes and counters, the same whatever
implements the step."""

from __future__ import annotations

from typing import Any, Dict

STATE_BYTES = 4         # the delta-rule state and the conv tails: float32
SMALL_BYTES = 4         # norm scales, A_log, dt_bias, conv taps: float32


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each kind are held."""
    types = config["layer_types"][:config["num_hidden_layers"]]
    gdn = sum(1 for t in types if t == "linear_attention")
    return {"gdn": gdn, "attention": len(types) - gdn, "all": len(types)}


def widths(config: Dict[str, Any]) -> Dict[str, int]:
    h = config["linear_num_key_heads"]
    return {"heads": h, "key": h * config["linear_key_head_dim"],
            "value": h * config["linear_value_head_dim"],
            "conv": 2 * h * config["linear_key_head_dim"]
            + h * config["linear_value_head_dim"]}


def parameters(config: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts: one GDN mixer's products (q, k, v, the gate, the
    output, the decay's and beta's inputs) and its small float32 leaves
    apart (the taps, A_log, dt_bias, the head norm, the norm after it), one
    attention mixer's products and its small leaves (the q/k norms, the norm
    after it), one SwiGLU and its norm, the head (the embedding is as
    large), the final norm."""
    d, f = config["hidden_size"], config["intermediate_size"]
    w = widths(config)
    hq = config["num_attention_heads"] * (d // config["num_attention_heads"])
    hkv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    return {"gdn": float(d * w["conv"] + d * w["value"] + w["value"] * d
                         + 2 * d * w["heads"]),
            "gdn_small": float(config["linear_conv_kernel_dim"] * w["conv"]
                               + 2 * w["heads"]
                               + config["linear_value_head_dim"] + d),
            "attention": float(2 * d * hq + 2 * d * hkv),
            "attention_small": float(hq + hkv + d),
            "mlp": 3.0 * d * f, "mlp_small": float(d),
            "head": float(d * config["vocab_size"]), "final_norm": float(d)}


def slot_state_numbers(config: Dict[str, Any]) -> float:
    """Numbers one slot keeps in one GDN layer: the state ``[H, d_k, d_v]``
    and the last ``K - 1`` inputs of the three convolutions."""
    w = widths(config)
    return float(w["heads"] * config["linear_key_head_dim"]
                 * config["linear_value_head_dim"]
                 + (config["linear_conv_kernel_dim"] - 1) * w["conv"])


def forward_flops(config: Dict[str, Any], new_tokens: int,
                  context_before: int = 0, logit_rows: int = None) -> float:
    """Forward operations to push ``new_tokens`` tokens of one sequence
    through every layer held here, the first of them at position
    ``context_before``. ``logit_rows``: how many of the tokens need logits
    (all by default)."""
    p, layers = parameters(config), layer_counts(config)
    n, c = new_tokens, context_before
    rows = n if logit_rows is None else logit_rows
    keys_seen = n * c + n * (n + 1) // 2
    d = config["hidden_size"]
    per_key = 2.0 * config["num_attention_heads"] * 2 \
        * (d // config["num_attention_heads"])
    recurrence = 6.0 * widths(config)["heads"] \
        * config["linear_key_head_dim"] * config["linear_value_head_dim"]
    return (layers["gdn"] * (2.0 * p["gdn"] + recurrence) * n
            + layers["attention"] * (2.0 * p["attention"] * n
                                     + per_key * keys_seen)
            + layers["all"] * 2.0 * p["mlp"] * n
            + 2.0 * p["head"] * rows)


def decode_step_bytes(config: Dict[str, Any], rows: int,
                      cached_tokens: float, live_slots: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step over ``rows`` slots must move:
    ``cached_tokens`` keys over all slots (the new ones included);
    ``live_slots`` slots whose state the step advances (read and written
    once each)."""
    p, layers = parameters(config), layer_counts(config)
    weights = (layers["gdn"] * (p["gdn"] * bytes_per_el
                                + p["gdn_small"] * SMALL_BYTES)
               + layers["attention"] * (p["attention"] * bytes_per_el
                                        + p["attention_small"] * SMALL_BYTES)
               + layers["all"] * (p["mlp"] * bytes_per_el
                                  + p["mlp_small"] * SMALL_BYTES)
               + p["head"] * bytes_per_el + p["final_norm"] * SMALL_BYTES)
    d = config["hidden_size"]
    row = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    kv = layers["attention"] * cached_tokens * 2 * row * bytes_per_el
    state = (2 * layers["gdn"] * live_slots * slot_state_numbers(config)
             * STATE_BYTES)
    return weights + rows * d * bytes_per_el + kv + state
