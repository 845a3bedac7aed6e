"""Per-leaf norms in the reference's naming, shared by both sides of the
comparison so that they are taken alike."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def leaf_norms(tree: dict) -> dict:
    """Norm of every leaf under a flat name; a stacked layer leaf
    (`tree["layers"][name]`, leading axis = layer) gives one entry a layer."""
    out = {}
    for name, leaf in tree.items():
        if name == "layers":
            for lname, stacked in leaf.items():
                per = jnp.sqrt(jnp.sum(jnp.square(stacked.astype(jnp.float32))
                                       .reshape(stacked.shape[0], -1), axis=1))
                for i in range(stacked.shape[0]):
                    out[f"layer{i}.{lname}"] = per[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
    return out


def moment_and_change(p: dict, m: dict, p0: dict) -> dict:
    """Norms of the first moment's leaves and of the leaves of `p - p0`."""
    return {"moment": leaf_norms(m),
            "change": leaf_norms(jax.tree.map(jnp.subtract, p, p0))}
