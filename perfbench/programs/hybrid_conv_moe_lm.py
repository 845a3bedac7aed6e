"""Adapter to the system under test: `flax_nets/llama.py`'s `LlamaLM` over a
hybrid decoder (gated short-convolution and full-attention layers, a dense
lead, one chip's share of sigmoid-routed experts, tied embedding), built from
a configuration file, and the maps between the reference's flat leaves
(`reference/hybrid_conv_moe_lm.py`) and its parameter and constants trees."""

from __future__ import annotations

# at import: a program without the mechanism fails here, at once
from synapseml_tpu.models.flax_nets.llama import LlamaLM, hybrid_conv_moe_lm

COLUMNS = ("input_ids", "labels")


def build(config: dict):
    # what the program holds as constants of this router and mixer
    if (config["routed_scaling_factor"], config["use_expert_bias"],
            config["conv_L_cache"], config["conv_bias"]) != (1, True, 3, False):
        raise ValueError("the program's sigmoid router scales its gates by 1 and chooses "
                         "with a selection bias; its short convolution has 3 taps, no bias")
    held = int(config["num_experts"])
    share = int(config["expert_share"].split(" of ")[0])
    cfg = hybrid_conv_moe_lm(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        n_layers=config["num_hidden_layers"], layer_types=tuple(config["layer_types"]),
        moe_dense_layers=config["num_dense_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], mlp_dim=config["intermediate_size"],
        moe_mlp_dim=config["moe_intermediate_size"], max_len=config["rope_table_len"],
        norm_eps=config["norm_eps"], rope_theta=float(config["rope_parameters"]["rope_theta"]),
        flash_block=config["flash_block"],
        moe_experts=held, moe_total_experts=config["published_num_experts"],
        moe_first_expert=share * held, moe_top_k=config["num_experts_per_tok"],
        remat=True)
    return LlamaLM(cfg)


def trainer_options(config: dict) -> dict:
    """The `TrainerConfig` fields the configuration file states: this model has
    no load-balance term (its router sows none; the weight says so too)."""
    return {"moe_aux_weight": float(config["moe_aux_weight"])}


def _layers(config: dict):
    """(index, is conv, has experts) of every layer."""
    dense = int(config["num_dense_layers"])
    return [(i, kind == "conv", i >= dense) for i, kind in enumerate(config["layer_types"])]


def to_program(p: dict, config: dict) -> dict:
    heads, kv, d = (config[k] for k in
                    ("num_attention_heads", "num_key_value_heads", "head_dim"))
    h = p["final_norm"].shape[0]
    decoder = {"RMSNorm_0": {"scale": p["final_norm"]}}
    for i, conv, experts in _layers(config):
        lp = {k.split(".", 1)[1]: v for k, v in p.items() if k.startswith(f"layer{i}.")}
        out = {"RMSNorm_0": {"scale": lp["ln1"]}, "RMSNorm_1": {"scale": lp["ln2"]}}
        if conv:
            out["conv"] = {"in_proj": {"kernel": lp["w_in"]}, "conv": lp["conv"],
                           "out_proj": {"kernel": lp["w_out"]}}
        else:
            out["attn"] = {
                "q": {"kernel": lp["wq"].reshape(h, heads, d)},
                "k": {"kernel": lp["wk"].reshape(h, kv, d)},
                "v": {"kernel": lp["wv"].reshape(h, kv, d)},
                "o": {"kernel": lp["wo"].reshape(heads, d, h)},
                "q_norm": {"scale": lp["q_norm"]}, "k_norm": {"scale": lp["k_norm"]}}
        if experts:
            out["mlp"] = {"router": {"kernel": lp["router"]}, "w_gate": lp["wg"],
                          "w_up": lp["wu"], "w_dn": lp["wd"]}
        else:
            out["mlp"] = {"gate": {"kernel": lp["w1"]}, "up": {"kernel": lp["w3"]},
                          "down": {"kernel": lp["w2"]}}
        decoder[f"layer_{i}"] = out
    return {"embed": {"embedding": p["embed"]}, "decoder": decoder}


def from_program(t: dict, config: dict) -> dict:
    heads, kv, d = (config[k] for k in
                    ("num_attention_heads", "num_key_value_heads", "head_dim"))
    dec = t["decoder"]
    h = t["embed"]["embedding"].shape[1]
    out = {"embed": t["embed"]["embedding"], "final_norm": dec["RMSNorm_0"]["scale"]}
    for i, conv, experts in _layers(config):
        l = dec[f"layer_{i}"]
        lp = {"ln1": l["RMSNorm_0"]["scale"], "ln2": l["RMSNorm_1"]["scale"]}
        if conv:
            lp.update(w_in=l["conv"]["in_proj"]["kernel"], conv=l["conv"]["conv"],
                      w_out=l["conv"]["out_proj"]["kernel"])
        else:
            a = l["attn"]
            lp.update(wq=a["q"]["kernel"].reshape(h, heads * d),
                      wk=a["k"]["kernel"].reshape(h, kv * d),
                      wv=a["v"]["kernel"].reshape(h, kv * d),
                      wo=a["o"]["kernel"].reshape(heads * d, h),
                      q_norm=a["q_norm"]["scale"], k_norm=a["k_norm"]["scale"])
        m = l["mlp"]
        if experts:
            lp.update(router=m["router"]["kernel"], wg=m["w_gate"], wu=m["w_up"], wd=m["w_dn"])
        else:
            lp.update(w1=m["gate"]["kernel"], w3=m["up"]["kernel"], w2=m["down"]["kernel"])
        out.update({f"layer{i}.{k}": v for k, v in lp.items()})
    return out


def constants_to_program(bias: dict, config: dict) -> dict:
    """The reference's `select_bias` as the module's 'constants' collection."""
    return {"decoder": {f"layer_{i}": {"mlp": {"select_bias": bias[f"layer{i}.beta"]}}
                        for i, _, experts in _layers(config) if experts}}
