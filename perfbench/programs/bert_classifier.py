"""Adapter to the system under test: `flax_nets/bert.py`'s `BertClassifier`
(what `DeepTextClassifier._fit` trains), built from a configuration file, and
the map between the reference's leaves and its parameter tree."""

from __future__ import annotations

from ._encoder_tree import encoder_from_program, encoder_to_program

COLUMNS = ("input_ids", "attention_mask", "labels")


def build(config: dict):
    from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_base

    cfg = bert_base(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        mlp_dim=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        norm_eps=config["layer_norm_eps"], dropout=config["hidden_dropout_prob"])
    return BertClassifier(cfg, num_classes=config["num_labels"])


def to_program(p: dict, config: dict) -> dict:
    return {
        "embeddings": {"word": {"embedding": p["word"]},
                       "position": {"embedding": p["position"]},
                       "segment": {"embedding": p["segment"]},
                       "LayerNorm_0": {"scale": p["emb_ln_g"], "bias": p["emb_ln_b"]}},
        "encoder": encoder_to_program(p["layers"], config["num_attention_heads"]),
        "pooler": {"kernel": p["pool_w"], "bias": p["pool_b"]},
        "classifier": {"kernel": p["head_w"], "bias": p["head_b"]}}


def from_program(t: dict, config: dict) -> dict:
    e = t["embeddings"]
    return {
        "word": e["word"]["embedding"], "position": e["position"]["embedding"],
        "segment": e["segment"]["embedding"],
        "emb_ln_g": e["LayerNorm_0"]["scale"], "emb_ln_b": e["LayerNorm_0"]["bias"],
        "layers": encoder_from_program(t["encoder"], config["num_hidden_layers"]),
        "pool_w": t["pooler"]["kernel"], "pool_b": t["pooler"]["bias"],
        "head_w": t["classifier"]["kernel"], "head_b": t["classifier"]["bias"]}
