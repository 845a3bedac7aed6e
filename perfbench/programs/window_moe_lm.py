"""Adapter to the system under test: `flax_nets/llama.py`'s `LlamaLM` over a
decoder of sliding-window and full attention layers (each kind its own query
heads and rotary, a sigmoid gate a head, a dense lead, one chip's share of
softmax-routed experts beside a shared expert, untied head), built from a
configuration file, and the maps between the reference's flat leaves
(`reference/window_moe_lm.py`) and its parameter tree."""

from __future__ import annotations

# at import: a program without the mechanism fails here, at once
from synapseml_tpu.models.flax_nets.llama import LlamaLM, window_moe_lm
from synapseml_tpu.models.flax_nets.transformer import AttnKind, Yarn

COLUMNS = ("input_ids", "labels")


def _kinds(config: dict) -> dict:
    """{layer kind: query heads} as the per-layer list gives them; one count a kind."""
    heads = {}
    for kind, n in zip(config["layer_types"], config["num_attention_heads_per_layer"]):
        heads.setdefault(kind, set()).add(int(n))
    if any(len(n) != 1 for n in heads.values()):
        raise ValueError(f"the program holds one head count a layer kind; the file states {heads}")
    return {kind: n.pop() for kind, n in heads.items()}


def _dense_layers(config: dict) -> int:
    """The leading dense layers; every later one has experts."""
    types = list(config["mlp_layer_types"])
    lead = types.index("sparse") if "sparse" in types else len(types)
    if set(types[:lead]) != {"dense"} or set(types[lead:]) - {"sparse"}:
        raise ValueError(f"the program's dense layers lead the stack; the file states {types}")
    return lead


def _attn_kind(rope: dict, heads: int, window: int) -> AttnKind:
    yarn = None
    if rope["rope_type"] == "yarn":
        yarn = Yarn(factor=float(rope["factor"]),
                    original_max_len=int(rope["original_max_position_embeddings"]),
                    beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
                    attention_factor=float(rope["attention_factor"]))
    elif rope["rope_type"] != "default":
        raise ValueError(f"the program's rotary is default or yarn, not {rope['rope_type']!r}")
    return AttnKind(heads=heads, window=window, rope_theta=float(rope["rope_theta"]),
                    rotary_fraction=float(rope["partial_rotary_factor"]), yarn=yarn)


def build(config: dict):
    # the forms this program has of the attention, the router and the head
    stated = (config["gating"], config["attention_bias"], config["tie_word_embeddings"],
              config["moe_apply_router_weight_on_input"],
              set(config["layer_types"]) <= {"full_attention", "sliding_attention"})
    if stated != (True, False, False, False, True):
        raise ValueError("the program's window decoder has a gate a head, no biases, an untied "
                         "head, router weights on the experts' outputs and full or sliding "
                         f"attention layers; the file states {stated}")
    heads = _kinds(config)
    window = int(config["sliding_window"])
    held = int(config["num_experts"])
    share = int(config["expert_share"].split(" of ")[0])
    cfg = window_moe_lm(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        n_layers=config["num_hidden_layers"], layer_types=tuple(config["layer_types"]),
        n_heads=heads.get("full_attention", config["num_attention_heads"]),
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        attn_kinds=tuple((kind, _attn_kind(config["rope_parameters"][kind], n,
                                           window if kind == "sliding_attention" else 0))
                         for kind, n in heads.items()),
        mlp_dim=config["intermediate_size"], moe_mlp_dim=config["moe_intermediate_size"],
        moe_shared_mlp_dim=config["shared_expert_intermediate_size"],
        moe_dense_layers=_dense_layers(config), max_len=config["rope_table_len"],
        norm_eps=config["rms_norm_eps"], flash_block=config["flash_block"],
        moe_experts=held, moe_total_experts=config["published_num_experts"],
        moe_first_expert=share * held, moe_top_k=config["num_experts_per_tok"],
        moe_gate_scale=float(config["moe_routed_scaling_factor"]), remat=True)
    return LlamaLM(cfg)


def trainer_options(config: dict) -> dict:
    """The `TrainerConfig` fields the configuration file states: no weight on
    the softmax router's load-balance term."""
    return {"moe_aux_weight": float(config["moe_aux_weight"])}


def _layers(config: dict):
    """(index, has experts) of every layer."""
    dense = _dense_layers(config)
    return [(i, i >= dense) for i in range(int(config["num_hidden_layers"]))]


def _attn_shapes(config: dict, i: int):
    return (int(config["num_attention_heads_per_layer"][i]), int(config["num_key_value_heads"]),
            int(config["head_dim"]))


def to_program(p: dict, config: dict) -> dict:
    h = p["final_norm"].shape[0]
    decoder = {"RMSNorm_0": {"scale": p["final_norm"]}}
    for i, experts in _layers(config):
        n, g, d = _attn_shapes(config, i)
        lp = {k.split(".", 1)[1]: w for k, w in p.items() if k.startswith(f"layer{i}.")}
        out = {"RMSNorm_0": {"scale": lp["ln1"]}, "RMSNorm_1": {"scale": lp["ln2"]},
               "attn": {"q": {"kernel": lp["wq"].reshape(h, n, d)},
                        "k": {"kernel": lp["wk"].reshape(h, g, d)},
                        "v": {"kernel": lp["wv"].reshape(h, g, d)},
                        "gate": {"kernel": lp["head_gate"]},
                        "o": {"kernel": lp["wo"].reshape(n, d, h)}}}
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
    dec = t["decoder"]
    h = t["embed"]["embedding"].shape[1]
    out = {"embed": t["embed"]["embedding"], "head": t["lm_head"]["kernel"],
           "final_norm": dec["RMSNorm_0"]["scale"]}
    for i, experts in _layers(config):
        n, g, d = _attn_shapes(config, i)
        l = dec[f"layer_{i}"]
        a, m = l["attn"], l["mlp"]
        lp = {"ln1": l["RMSNorm_0"]["scale"], "ln2": l["RMSNorm_1"]["scale"],
              "wq": a["q"]["kernel"].reshape(h, n * d), "wk": a["k"]["kernel"].reshape(h, g * d),
              "wv": a["v"]["kernel"].reshape(h, g * d), "head_gate": a["gate"]["kernel"],
              "wo": a["o"]["kernel"].reshape(n * d, h)}
        if experts:
            s = m["shared"]
            lp.update(router=m["router"]["kernel"], wg=m["w_gate"], wu=m["w_up"], wd=m["w_dn"],
                      sg=s["gate"]["kernel"], su=s["up"]["kernel"], sd=s["down"]["kernel"])
        else:
            lp.update(w1=m["gate"]["kernel"], w3=m["up"]["kernel"], w2=m["down"]["kernel"])
        out.update({f"layer{i}.{k}": w for k, w in lp.items()})
    return out


def constants_to_program(bias: dict, config: dict):
    """No constants: the softmax router has no selection bias."""
    return None
