"""Adapter to the system under test: `flax_nets/llama.py`'s `LlamaLM` over a
latent-attention decoder (multi-head latent attention, a dense lead, one
chip's share of sigmoid-routed experts beside a shared expert, untied head),
built from a configuration file, and the maps between the reference's flat
leaves (`reference/latent_moe_lm.py`) and its parameter and constants trees."""

from __future__ import annotations

# at import: a program without the mechanism fails here, at once
from synapseml_tpu.models.flax_nets.llama import LlamaLM, latent_moe_lm

COLUMNS = ("input_ids", "labels")


def build(config: dict):
    # the forms this program has of the router, the queries and the head
    stated = (config["scoring_func"], config["topk_method"], config["n_group"],
              config["topk_group"], config["norm_topk_prob"], config["q_lora_rank"],
              config["attention_bias"], config["hidden_act"], config["tie_word_embeddings"])
    if stated != ("sigmoid", "noaux_tc", 1, 1, True, None, False, "silu", False):
        raise ValueError("the program's latent decoder has a sigmoid router with a selection "
                         "bias and one group, normalised gates, directly projected queries, "
                         f"no biases, SiLU and an untied head; the file states {stated}")
    held = int(config["n_routed_experts"])
    share = int(config["expert_share"].split(" of ")[0])
    cfg = latent_moe_lm(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        n_layers=config["num_hidden_layers"], moe_dense_layers=config["first_k_dense_replace"],
        n_heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        kv_latent_rank=config["kv_lora_rank"], mlp_dim=config["intermediate_size"],
        moe_mlp_dim=config["moe_intermediate_size"],
        moe_shared_mlp_dim=config["n_shared_experts"] * config["moe_intermediate_size"],
        max_len=config["rope_table_len"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), flash_block=config["flash_block"],
        moe_experts=held, moe_total_experts=config["published_n_routed_experts"],
        moe_first_expert=share * held, moe_top_k=config["num_experts_per_tok"],
        moe_gate_scale=float(config["routed_scaling_factor"]),
        moe_gate_eps=float(config["gate_normalisation_eps"]), remat=True)
    return LlamaLM(cfg)


def trainer_options(config: dict) -> dict:
    """The `TrainerConfig` fields the configuration file states: this model has
    no load-balance term (its router sows none; the weight says so too)."""
    return {"moe_aux_weight": float(config["moe_aux_weight"])}


def _layers(config: dict):
    """(index, has experts) of every layer."""
    dense = int(config["first_k_dense_replace"])
    return [(i, i >= dense) for i in range(int(config["num_hidden_layers"]))]


def _widths(config: dict):
    return (int(config["num_attention_heads"]), int(config["qk_nope_head_dim"]),
            int(config["qk_rope_head_dim"]), int(config["v_head_dim"]))


def to_program(p: dict, config: dict) -> dict:
    heads, n, r, v = _widths(config)
    h = p["final_norm"].shape[0]
    decoder = {"RMSNorm_0": {"scale": p["final_norm"]}}
    for i, experts in _layers(config):
        lp = {k.split(".", 1)[1]: w for k, w in p.items() if k.startswith(f"layer{i}.")}
        out = {"RMSNorm_0": {"scale": lp["ln1"]}, "RMSNorm_1": {"scale": lp["ln2"]},
               "attn": {"q": {"kernel": lp["wq"].reshape(h, heads, n + r)},
                        "kv_a": {"kernel": lp["wa"]}, "kv_norm": {"scale": lp["kv_norm"]},
                        "kv_b": {"kernel": lp["wb"].reshape(-1, heads, n + v)},
                        "o": {"kernel": lp["wo"].reshape(heads, v, h)}}}
        if experts:
            out["mlp"] = {"router": {"kernel": lp["router"]}, "w_gate": lp["wg"],
                          "w_up": lp["wu"], "w_dn": lp["wd"],
                          "shared": {"gate": {"kernel": lp["sg"]}, "up": {"kernel": lp["su"]},
                                     "down": {"kernel": lp["sd"]}}}
        else:
            out["mlp"] = {"gate": {"kernel": lp["w1"]}, "up": {"kernel": lp["w3"]},
                          "down": {"kernel": lp["w2"]}}
        decoder[f"layer_{i}"] = out
    return {"embed": {"embedding": p["embed"]}, "decoder": decoder,
            "lm_head": {"kernel": p["head"]}}


def from_program(t: dict, config: dict) -> dict:
    heads, n, r, v = _widths(config)
    dec = t["decoder"]
    h = t["embed"]["embedding"].shape[1]
    out = {"embed": t["embed"]["embedding"], "head": t["lm_head"]["kernel"],
           "final_norm": dec["RMSNorm_0"]["scale"]}
    for i, experts in _layers(config):
        l = dec[f"layer_{i}"]
        a, m = l["attn"], l["mlp"]
        lp = {"ln1": l["RMSNorm_0"]["scale"], "ln2": l["RMSNorm_1"]["scale"],
              "wq": a["q"]["kernel"].reshape(h, heads * (n + r)), "wa": a["kv_a"]["kernel"],
              "kv_norm": a["kv_norm"]["scale"],
              "wb": a["kv_b"]["kernel"].reshape(-1, heads * (n + v)),
              "wo": a["o"]["kernel"].reshape(heads * v, h)}
        if experts:
            s = m["shared"]
            lp.update(router=m["router"]["kernel"], wg=m["w_gate"], wu=m["w_up"], wd=m["w_dn"],
                      sg=s["gate"]["kernel"], su=s["up"]["kernel"], sd=s["down"]["kernel"])
        else:
            lp.update(w1=m["gate"]["kernel"], w3=m["up"]["kernel"], w2=m["down"]["kernel"])
        out.update({f"layer{i}.{k}": w for k, w in lp.items()})
    return out


def constants_to_program(bias: dict, config: dict) -> dict:
    """The reference's `select_bias` as the module's 'constants' collection."""
    return {"decoder": {f"layer_{i}": {"mlp": {"select_bias": bias[f"layer{i}.beta"]}}
                        for i, experts in _layers(config) if experts}}
