"""Operations a training step of a hybrid conv-attention MoE decoder needs,
from shapes: what the mathematics asks for, whatever computes it.

Counted, forward, a token: in a 'conv' layer the input projection to three
streams and the output projection; in a 'full_attention' layer the q/k/v/o
projections and the score and value products over the causal pairs (s <= t);
in a dense layer the gated MLP's three products; in an expert layer the router
over all the model's experts and the held experts' three products for the
pairs expected here (experts a token x held / all, uniform routing). Once a
token: the head over the held vocabulary. Training is 3x the forward
(gradient to the input and to the weight). Not counted: the embedding
look-up, norms, the convolution's taps and gates (3 + 2 multiply-adds a
channel), softmax, sigmoid, the top-k, the optimizer, and anything recomputed
or computed and masked.

`flash_forward_flops`: what ONE launch of the flash kernel's forward computes
at least: the score and value products of one row's causal pairs.
"""

from __future__ import annotations


def tokens_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["text"]["seq_len"])       # packed: every row is full


def forward_matmul_flops(config: dict, tokens: int) -> dict:
    """Forward-pass matmul FLOPs of ONE sample of `tokens` tokens, by part."""
    h = int(config["hidden_size"])
    heads, kv, d = (int(config[k]) for k in
                    ("num_attention_heads", "num_key_value_heads", "head_dim"))
    kinds = list(config["layer_types"])
    n_conv, n_attn = kinds.count("conv"), kinds.count("full_attention")
    n_dense = int(config["num_dense_layers"])
    n_moe = len(kinds) - n_dense
    causal = tokens * (tokens + 1) // 2
    held_pairs = tokens * int(config["num_experts_per_tok"]) \
        * int(config["num_experts"]) / int(config["published_num_experts"])
    return {
        "conv_proj": n_conv * tokens * 2 * h * (3 * h + h),
        "qkvo": n_attn * tokens * 2 * h * d * (2 * heads + 2 * kv),
        "attn_scores": n_attn * causal * 2 * heads * d,
        "attn_values": n_attn * causal * 2 * heads * d,
        "dense_mlp": n_dense * tokens * 3 * 2 * h * int(config["intermediate_size"]),
        "router": n_moe * tokens * 2 * h * int(config["published_num_experts"]),
        "experts": n_moe * held_pairs * 3 * 2 * h * int(config["moe_intermediate_size"]),
        "head": tokens * 2 * h * int(config["vocab_size"])}


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    return float(3 * sum(forward_matmul_flops(
        config, tokens_per_sample(config, traffic)).values()))


def flash_forward_flops(config: dict, traffic: dict) -> float:
    """One launch of the flash forward kernel on one row: 2 products x 2 x
    heads x head_dim x the causal pairs (head_dim as the mathematics has it,
    not as the kernel pads it)."""
    t = tokens_per_sample(config, traffic)
    return float(4 * int(config["num_attention_heads"]) * int(config["head_dim"])
                 * (t * (t + 1) // 2))
