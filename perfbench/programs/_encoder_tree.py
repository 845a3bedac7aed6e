"""The shared part of the two adapters: the reference's stacked encoder leaves
to and from `flax_nets/transformer.py`'s `Encoder` tree (`layer_<i>/...`)."""

from __future__ import annotations

import jax.numpy as jnp


def encoder_to_program(layers: dict, heads: int) -> dict:
    n, h, _ = layers["wq"].shape
    d = h // heads
    out = {}
    for i in range(n):
        lp = {k: v[i] for k, v in layers.items()}
        out[f"layer_{i}"] = {
            "attn": {
                "q": {"kernel": lp["wq"].reshape(h, heads, d), "bias": lp["bq"].reshape(heads, d)},
                "k": {"kernel": lp["wk"].reshape(h, heads, d), "bias": lp["bk"].reshape(heads, d)},
                "v": {"kernel": lp["wv"].reshape(h, heads, d), "bias": lp["bv"].reshape(heads, d)},
                "o": {"kernel": lp["wo"].reshape(heads, d, h), "bias": lp["bo"]}},
            "LayerNorm_0": {"scale": lp["ln1_g"], "bias": lp["ln1_b"]},
            "LayerNorm_1": {"scale": lp["ln2_g"], "bias": lp["ln2_b"]},
            "mlp": {"up": {"kernel": lp["w1"], "bias": lp["b1"]},
                    "down": {"kernel": lp["w2"], "bias": lp["b2"]}}}
    return out


def encoder_from_program(enc: dict, n_layers: int) -> dict:
    def stack(pick):
        return jnp.stack([pick(enc[f"layer_{i}"]) for i in range(n_layers)])

    h = enc["layer_0"]["attn"]["o"]["bias"].shape[0]
    return {
        "wq": stack(lambda l: l["attn"]["q"]["kernel"].reshape(h, h)),
        "bq": stack(lambda l: l["attn"]["q"]["bias"].reshape(h)),
        "wk": stack(lambda l: l["attn"]["k"]["kernel"].reshape(h, h)),
        "bk": stack(lambda l: l["attn"]["k"]["bias"].reshape(h)),
        "wv": stack(lambda l: l["attn"]["v"]["kernel"].reshape(h, h)),
        "bv": stack(lambda l: l["attn"]["v"]["bias"].reshape(h)),
        "wo": stack(lambda l: l["attn"]["o"]["kernel"].reshape(h, h)),
        "bo": stack(lambda l: l["attn"]["o"]["bias"]),
        "ln1_g": stack(lambda l: l["LayerNorm_0"]["scale"]),
        "ln1_b": stack(lambda l: l["LayerNorm_0"]["bias"]),
        "ln2_g": stack(lambda l: l["LayerNorm_1"]["scale"]),
        "ln2_b": stack(lambda l: l["LayerNorm_1"]["bias"]),
        "w1": stack(lambda l: l["mlp"]["up"]["kernel"]),
        "b1": stack(lambda l: l["mlp"]["up"]["bias"]),
        "w2": stack(lambda l: l["mlp"]["down"]["kernel"]),
        "b2": stack(lambda l: l["mlp"]["down"]["bias"])}
