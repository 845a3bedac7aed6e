"""What a rematerialised stack keeps of the flash kernel, for the test files of
the two decoder families that run it: the kernel launches of a jaxpr, the
stack's remat as it was before it kept anything, and the comparison of two
gradient trees bit for bit."""

import flax.linen as nn
import jax
import numpy as np


def pallas_eqns(jaxpr):
    """The `pallas_call` equations of a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += pallas_eqns(sub)
    return found


def keep_nothing(monkeypatch):
    """`Encoder`'s remat with no policy (`transformer.nn` is this module)."""
    remat = nn.remat
    monkeypatch.setattr(nn, "remat", lambda cls, **_: remat(cls, static_argnums=()))


def assert_bit_equal(got, want):
    """Two gradient trees, leaf by leaf: the kept output is the one a second
    launch would write, so nothing may differ."""
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), jax.tree_util.keystr(path)
