"""Operations a training step of a decoder of sliding-window and full
attention layers with routed experts needs, from shapes: what the mathematics
asks for, whatever computes it.

Counted, forward, a token: in every layer the attention's projections (q to
its kind's heads x head_dim, k and v to the key/value heads, the heads' gates,
o back) and the score and value products at head_dim over the pairs the kind
keeps: every causal pair (s <= t) on a full layer, the band (t - window < s
<= t) on a sliding one; in a dense layer the gated MLP's three products; in an
expert layer the router over all the model's experts, the held experts' three
products for the pairs expected here (experts a token x held / all, uniform
routing) and the shared expert's three products for every token. Once a
token: the head over the held vocabulary. Training is 3x the forward
(gradient to the input and to the weight). Not counted: the embedding
look-up, norms, RoPE, softmax, sigmoid, the top-k, the optimizer, and
anything recomputed or computed and masked or padded.

`window_forward_flops`: what ONE launch of the windowed flash kernel's
forward computes at least on one row: the score and value products of the
band's pairs at head_dim (128, the kernel's own lane width: nothing padded),
not the tiles' pairs outside the band.
"""

from __future__ import annotations


def tokens_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["text"]["seq_len"])       # packed: every row is full


def band_pairs(tokens: int, window: int) -> int:
    """(query, key) pairs with t - window < s <= t over `tokens` positions."""
    w = min(window, tokens)
    return w * (w + 1) // 2 + (tokens - w) * w


def forward_matmul_flops(config: dict, tokens: int) -> dict:
    """Forward-pass matmul FLOPs of ONE sample of `tokens` tokens, by part."""
    h, d, g = (int(config[k]) for k in ("hidden_size", "head_dim", "num_key_value_heads"))
    width = int(config["moe_intermediate_size"])
    held_pairs = tokens * int(config["num_experts_per_tok"]) \
        * int(config["num_experts"]) / int(config["published_num_experts"])
    keys = {"full_attention": tokens * (tokens + 1) // 2,
            "sliding_attention": band_pairs(tokens, int(config["sliding_window"]))}
    out = dict.fromkeys(("full_proj", "full_attn", "sliding_proj", "sliding_attn", "dense_mlp",
                         "shared_expert", "router", "experts"), 0.0)
    for kind, n, mlp in zip(config["layer_types"], config["num_attention_heads_per_layer"],
                            config["mlp_layer_types"]):
        part = kind.split("_")[0]
        out[f"{part}_proj"] += tokens * 2 * (2 * h * n * d + 2 * h * g * d + h * n)
        out[f"{part}_attn"] += keys[kind] * 2 * n * 2 * d
        if mlp == "dense":
            out["dense_mlp"] += tokens * 3 * 2 * h * int(config["intermediate_size"])
        else:
            out["shared_expert"] += tokens * 3 * 2 * h * int(
                config["shared_expert_intermediate_size"])
            out["router"] += tokens * 2 * h * int(config["published_num_experts"])
            out["experts"] += held_pairs * 3 * 2 * h * width
    out["head"] = tokens * 2 * h * int(config["vocab_size"])
    return out


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    return float(3 * sum(forward_matmul_flops(
        config, tokens_per_sample(config, traffic)).values()))


def window_forward_flops(config: dict, traffic: dict) -> float:
    """One launch of the windowed flash forward kernel on one row: 2 x heads
    x (head_dim + head_dim) x the band's pairs, at the sliding layers' heads."""
    heads = {int(n) for kind, n in zip(config["layer_types"],
                                       config["num_attention_heads_per_layer"])
             if kind == "sliding_attention"}
    if len(heads) != 1:
        raise ValueError(f"one head count on the sliding layers, not {sorted(heads)}")
    return float(2 * heads.pop() * 2 * int(config["head_dim"]) * band_pairs(
        tokens_per_sample(config, traffic), int(config["sliding_window"])))
