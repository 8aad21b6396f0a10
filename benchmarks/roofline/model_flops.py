"""Operations a model's forward and backward passes REQUIRE, from its shapes.
Recomputed operations do not count, so these are below what the compiler's
``cost_analysis`` reports for a step that recomputes."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def lm_forward_flops(config: Dict[str, Any], new_tokens: int,
                     context_before: int = 0, logit_rows: int = None) -> float:
    """Forward operations to push ``new_tokens`` tokens of one sequence
    through the model, the first of them at position ``context_before``:
    2 per multiply-add of every product with a weight (q, k, v, o, the two
    MLP products, the output head) and, for attention, the scores and the
    weighted sum over the keys each token may see (itself and all before).
    ``logit_rows``: how many of the tokens need logits (all by default; a
    prefill chunk needs one or none)."""
    d, f, v, l = (config["hidden_size"], config["intermediate_size"],
                  config["vocab_size"], config["num_hidden_layers"])
    a = config["num_attention_heads"] * (d // config["num_attention_heads"])
    n, c = new_tokens, context_before
    rows = n if logit_rows is None else logit_rows
    keys_seen = n * c + n * (n + 1) // 2
    return (2.0 * l * (4 * d * a + 2 * d * f) * n + 2.0 * d * v * rows
            + 2.0 * 2.0 * a * keys_seen * l)


def lm_train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward = 3 x forward, per token of a packed sequence."""
    return 3.0 * lm_forward_flops(config, seq) / seq


def resnet_convs(config: Dict[str, Any]) -> List[Tuple[int, int, int, int, int]]:
    """(kernel, c_in, c_out, out_h, out_w) of every convolution of the
    bottleneck ResNet (v1.5: the 3x3 carries the stride), then the classifier
    as a 1x1 on a 1x1 map."""
    size = config["image_size"]
    width = config["num_filters"]
    convs = []
    h = size // 2
    convs.append((7, config["num_channels"], width, h, h))
    h //= 2                                     # max pool
    c_in = width
    for i, blocks in enumerate(config["stage_sizes"]):
        mid = width * 2 ** i
        out = mid * config["bottleneck_expansion"]
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            convs.append((1, c_in, mid, h, h))
            h_out = h // stride
            convs.append((3, mid, mid, h_out, h_out))
            convs.append((1, mid, out, h_out, h_out))
            if c_in != out or stride != 1:
                convs.append((1, c_in, out, h_out, h_out))
            c_in, h = out, h_out
    convs.append((1, c_in, config["num_classes"], 1, 1))
    return convs


def resnet_train_flops_per_image(config: Dict[str, Any]) -> float:
    """Forward + gradient w.r.t. weights + gradient w.r.t. inputs, each as
    many operations as the forward; the first convolution's input is the
    image and needs no gradient."""
    total = 0.0
    for i, (k, c_in, c_out, h, w) in enumerate(resnet_convs(config)):
        fwd = 2.0 * k * k * c_in * c_out * h * w
        total += fwd * (2.0 if i == 0 else 3.0)
    return total
