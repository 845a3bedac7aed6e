"""Operations a training step of an encoder classifier needs, from shapes.

Counted: the matrix products of the forward pass and of the backward pass
(gradient to the input and to the weight, so 3x the forward's, 2x where the
input needs no gradient), attention's score and value products included.
Not counted: embedding look-ups and their scatter-add, LayerNorm, softmax,
GELU, the optimizer, and anything recomputed.
"""

from __future__ import annotations


def tokens_per_sample(config: dict, traffic: dict) -> int:
    if config["inputs"] == "text":
        return int(traffic["text"]["seq_len"])      # every row is padded to it
    return 1 + (int(config["image_size"]) // int(config["patch_size"])) ** 2


def forward_matmul_flops(config: dict, tokens: int) -> dict:
    """Forward-pass matmul FLOPs of ONE sample, by part."""
    h, m = int(config["hidden_size"]), int(config["intermediate_size"])
    n, c = int(config["num_hidden_layers"]), int(config["num_labels"])
    parts = {
        "qkvo": n * tokens * 2 * 4 * h * h,
        "mlp": n * tokens * 2 * 2 * h * m,
        "scores": n * tokens * 2 * tokens * h,     # q.k over all heads
        "values": n * tokens * 2 * tokens * h,     # probs.v over all heads
        "head": 2 * h * c}
    if config["inputs"] == "text":
        parts["pooler"] = 2 * h * h
    else:
        k = int(config["patch_size"]) ** 2 * int(config["num_channels"])
        parts["patch_embed"] = (tokens - 1) * 2 * k * h
    return parts


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    parts = forward_matmul_flops(config, tokens_per_sample(config, traffic))
    # pixels need no gradient: the patch embedding has no input-side product
    return float(sum((2 if name == "patch_embed" else 3) * f
                     for name, f in parts.items()))
