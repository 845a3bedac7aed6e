"""Operations a training step of a latent-attention MoE decoder needs, from
shapes: what the mathematics asks for, whatever computes it.

Counted, forward, a token: in every layer the latent mixer's four projections
(queries to heads x (nope + rope); the down-projection to the latent plus the
shared rotary key; the up-projection of the latent to heads x (nope + value);
the output projection from heads x value) and the score products over the
causal pairs (s <= t) at nope + rope dims and the value products at the value
width; in a dense layer the gated MLP's three products; in an expert layer
the router over all the model's experts, the held experts' three products for
the pairs expected here (experts a token x held / all, uniform routing) and
the shared expert's three products for every token. Once a token: the head
over the held vocabulary. Training is 3x the forward (gradient to the input
and to the weight). Not counted: the embedding look-up, norms, RoPE, softmax,
sigmoid, the top-k, the optimizer, and anything recomputed or computed and
masked or padded.

`flash_forward_flops`: what ONE launch of the flash kernel's forward computes
at least on one row: the score and value products of its causal pairs at the
true widths (192 and 128), not the lanes the kernel pads to (256 and 128).
"""

from __future__ import annotations


def tokens_per_sample(config: dict, traffic: dict) -> int:
    return int(traffic["text"]["seq_len"])       # packed: every row is full


def forward_matmul_flops(config: dict, tokens: int) -> dict:
    """Forward-pass matmul FLOPs of ONE sample of `tokens` tokens, by part."""
    h = int(config["hidden_size"])
    heads, n, r, v, lat = (int(config[k]) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank"))
    layers = int(config["num_hidden_layers"])
    n_dense = int(config["first_k_dense_replace"])
    n_moe = layers - n_dense
    width = int(config["moe_intermediate_size"])
    causal = tokens * (tokens + 1) // 2
    held_pairs = tokens * int(config["num_experts_per_tok"]) \
        * int(config["n_routed_experts"]) / int(config["published_n_routed_experts"])
    return {
        "latent_proj": layers * tokens * 2 * (h * heads * (n + r) + h * (lat + r)
                                              + lat * heads * (n + v) + heads * v * h),
        "attn_scores": layers * causal * 2 * heads * (n + r),
        "attn_values": layers * causal * 2 * heads * v,
        "dense_mlp": n_dense * tokens * 3 * 2 * h * int(config["intermediate_size"]),
        "shared_expert": n_moe * tokens * 3 * 2 * h * int(config["n_shared_experts"]) * width,
        "router": n_moe * tokens * 2 * h * int(config["published_n_routed_experts"]),
        "experts": n_moe * held_pairs * 3 * 2 * h * width,
        "head": tokens * 2 * h * int(config["vocab_size"])}


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    return float(3 * sum(forward_matmul_flops(
        config, tokens_per_sample(config, traffic)).values()))


def flash_forward_flops(config: dict, traffic: dict) -> float:
    """One launch of the flash forward kernel on one row: 2 x heads x
    (nope + rope + value) x the causal pairs."""
    t = tokens_per_sample(config, traffic)
    return float(2 * int(config["num_attention_heads"])
                 * (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
                    + int(config["v_head_dim"])) * (t * (t + 1) // 2))
