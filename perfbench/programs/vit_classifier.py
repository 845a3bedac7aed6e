"""Adapter to the system under test: `flax_nets/vit.py`'s `ViTClassifier`
(what `DeepVisionClassifier._fit` trains), built from a configuration file,
and the map between the reference's leaves and its parameter tree."""

from __future__ import annotations

from ._encoder_tree import encoder_from_program, encoder_to_program

COLUMNS = ("x", "labels")


def build(config: dict):
    from synapseml_tpu.models.flax_nets.vit import ViTClassifier, vit_b16

    ps, size = config["patch_size"], config["image_size"]
    cfg = vit_b16(
        hidden=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], mlp_dim=config["intermediate_size"],
        max_len=1 + (size // ps) ** 2, norm_eps=config["layer_norm_eps"],
        dropout=config["hidden_dropout_prob"])
    return ViTClassifier(cfg, num_classes=config["num_labels"], patch=ps)


def to_program(p: dict, config: dict) -> dict:
    ps, c, h = config["patch_size"], config["num_channels"], config["hidden_size"]
    enc = encoder_to_program(p["layers"], config["num_attention_heads"])
    enc["LayerNorm_0"] = {"scale": p["final_ln_g"], "bias": p["final_ln_b"]}
    return {"patch_embed": {"kernel": p["patch_w"].reshape(ps, ps, c, h),
                            "bias": p["patch_b"]},
            "cls": p["cls"].reshape(1, 1, h), "pos_embed": p["pos"][None],
            "encoder": enc,
            "head": {"kernel": p["head_w"], "bias": p["head_b"]}}


def from_program(t: dict, config: dict) -> dict:
    h = config["hidden_size"]
    return {"patch_w": t["patch_embed"]["kernel"].reshape(-1, h),
            "patch_b": t["patch_embed"]["bias"], "cls": t["cls"].reshape(h),
            "pos": t["pos_embed"][0],
            "final_ln_g": t["encoder"]["LayerNorm_0"]["scale"],
            "final_ln_b": t["encoder"]["LayerNorm_0"]["bias"],
            "layers": encoder_from_program(t["encoder"], config["num_hidden_layers"]),
            "head_w": t["head"]["kernel"], "head_b": t["head"]["bias"]}
