"""Operations a training step of a sparse-attention MoE decoder needs, from
shapes: what the mathematics asks for, whatever computes it.

Counted, forward, a token and a layer: the q/k/v/o projections; the indexer's
projections; the indexer's head scores over the causal pairs (s <= t); the
score and value products over the SELECTED pairs only (min(t + 1, topk) a
query); the router over all the model's experts; the held experts' three
products for the pairs expected here (experts a token x held / all, uniform
routing). Once a token: the head over the held vocabulary. Training is 3x the
forward (gradient to the input and to the weight), 2x for the indexer's
projections, whose input is read under stop_gradient. Not counted: the
embedding look-up, norms, softmax, the top-k selection, the indexer's sum over
its heads, the optimizer, and anything recomputed or computed and masked.
"""

from __future__ import annotations


def tokens_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["text"]["seq_len"])       # packed: every row is full


def forward_matmul_flops(config: dict, tokens: int) -> dict:
    """Forward-pass matmul FLOPs of ONE sample of `tokens` tokens, by part."""
    h, n = int(config["hidden_size"]), int(config["num_hidden_layers"])
    heads, kv, d = (int(config[k]) for k in
                    ("num_attention_heads", "num_key_value_heads", "head_dim"))
    sa = config["sa_config"]
    hi, di, topk = (int(sa[k]) for k in ("indexer_num_heads", "indexer_head_dim", "topk"))
    causal = tokens * (tokens + 1) // 2
    selected = sum(min(t + 1, topk) for t in range(tokens))
    held_pairs = tokens * int(config["num_experts_per_tok"]) \
        * int(config["num_experts"]) / int(config["published_num_experts"])
    return {
        "qkvo": n * tokens * 2 * h * d * (2 * heads + 2 * kv),
        "indexer_proj": n * tokens * 2 * h * (hi * di + di + hi),
        "indexer_scores": n * causal * 2 * hi * di,
        "selected_scores": n * selected * 2 * heads * d,
        "selected_values": n * selected * 2 * heads * d,
        "router": n * tokens * 2 * h * int(config["published_num_experts"]),
        "experts": n * held_pairs * 3 * 2 * h * int(config["moe_intermediate_size"]),
        "head": tokens * 2 * h * int(config["vocab_size"])}


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    parts = forward_matmul_flops(config, tokens_per_sample(config, traffic))
    return float(sum((2 if name == "indexer_proj" else 3) * f for name, f in parts.items()))
