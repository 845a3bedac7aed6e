"""Adapter to the system under test: `flax_nets/llama.py`'s `LlamaLM` over a
decoder with learned sparse attention and one chip's share of the routed
experts, built from a configuration file, and the map between the reference's
leaves (`reference/sparse_moe_lm.py`) and its parameter tree."""

from __future__ import annotations

import jax.numpy as jnp

# at import: a program without the mechanism fails here, at once
from synapseml_tpu.models.flax_nets.llama import LlamaLM, sparse_moe_lm

COLUMNS = ("input_ids", "labels")


def build(config: dict):
    sa = config["sa_config"]
    held = int(config["num_experts"])
    share = int(config["expert_share"].split(" of ")[0])
    cfg = sparse_moe_lm(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        mlp_dim=config["moe_intermediate_size"], max_len=config["rope_table_len"],
        norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        act=config["hidden_act"], attn_bias=config["attention_bias"],
        attn_topk=sa["topk"], indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], attn_q_tile=config["attn_q_tile"],
        moe_experts=held, moe_total_experts=config["published_num_experts"],
        moe_first_expert=share * held, moe_top_k=config["num_experts_per_tok"],
        remat=True)
    return LlamaLM(cfg)


def trainer_options(config: dict) -> dict:
    """The `TrainerConfig` fields the configuration file states: the weights of
    the two loss terms the model sows (the reference reads the same keys)."""
    return {"moe_aux_weight": float(config["moe_aux_weight"]),
            "indexer_loss_weight": float(config["indexer_loss_weight"])}


def _sizes(config: dict):
    sa = config["sa_config"]
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], sa["indexer_num_heads"], sa["indexer_head_dim"])


def to_program(p: dict, config: dict) -> dict:
    heads, kv, d, hi, di = _sizes(config)
    layers = p["layers"]
    n, h = layers["ln1"].shape
    decoder = {"RMSNorm_0": {"scale": p["final_norm"]}}
    for i in range(n):
        lp = {k: v[i] for k, v in layers.items()}
        decoder[f"layer_{i}"] = {
            "RMSNorm_0": {"scale": lp["ln1"]}, "RMSNorm_1": {"scale": lp["ln2"]},
            "attn": {
                "q": {"kernel": lp["wq"].reshape(h, heads, d)},
                "k": {"kernel": lp["wk"].reshape(h, kv, d)},
                "v": {"kernel": lp["wv"].reshape(h, kv, d)},
                "o": {"kernel": lp["wo"].reshape(heads, d, h)},
                "q_norm": {"scale": lp["q_norm"]}, "k_norm": {"scale": lp["k_norm"]},
                "indexer_q": {"kernel": lp["iq"].reshape(h, hi, di)},
                "indexer_k": {"kernel": lp["ik"]}, "indexer_w": {"kernel": lp["iw"]}},
            "mlp": {"router": {"kernel": lp["router"]}, "w_gate": lp["wg"],
                    "w_up": lp["wu"], "w_dn": lp["wd"]}}
    return {"embed": {"embedding": p["embed"]}, "decoder": decoder,
            "lm_head": {"kernel": p["head"]}}


def from_program(t: dict, config: dict) -> dict:
    heads, kv, d, hi, di = _sizes(config)
    dec = t["decoder"]
    h = t["embed"]["embedding"].shape[1]

    def stack(pick):
        return jnp.stack([pick(dec[f"layer_{i}"])
                          for i in range(config["num_hidden_layers"])])

    return {
        "embed": t["embed"]["embedding"], "final_norm": dec["RMSNorm_0"]["scale"],
        "head": t["lm_head"]["kernel"],
        "layers": {
            "ln1": stack(lambda l: l["RMSNorm_0"]["scale"]),
            "ln2": stack(lambda l: l["RMSNorm_1"]["scale"]),
            "wq": stack(lambda l: l["attn"]["q"]["kernel"].reshape(h, heads * d)),
            "wk": stack(lambda l: l["attn"]["k"]["kernel"].reshape(h, kv * d)),
            "wv": stack(lambda l: l["attn"]["v"]["kernel"].reshape(h, kv * d)),
            "wo": stack(lambda l: l["attn"]["o"]["kernel"].reshape(heads * d, h)),
            "q_norm": stack(lambda l: l["attn"]["q_norm"]["scale"]),
            "k_norm": stack(lambda l: l["attn"]["k_norm"]["scale"]),
            "iq": stack(lambda l: l["attn"]["indexer_q"]["kernel"].reshape(h, hi * di)),
            "ik": stack(lambda l: l["attn"]["indexer_k"]["kernel"]),
            "iw": stack(lambda l: l["attn"]["indexer_w"]["kernel"]),
            "router": stack(lambda l: l["mlp"]["router"]["kernel"]),
            "wg": stack(lambda l: l["mlp"]["w_gate"]),
            "wu": stack(lambda l: l["mlp"]["w_up"]),
            "wd": stack(lambda l: l["mlp"]["w_dn"])}}
