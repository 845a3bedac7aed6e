"""Gated short convolution: the token mixer of hybrid conv-attention decoders.

``y[t] = c[t] * sum_j w[:, j] * a[t - (L - 1) + j]``, ``a = b * u``, ``a[s] = 0``
for ``s < 0``: a gate, a depthwise causal convolution of ``L`` taps along the
sequence (tap ``L - 1`` multiplies the current position, as a PyTorch
``Conv1d(groups=H, padding=L - 1)`` cut to ``T`` does), a second gate.

Plain XLA: ``L`` shifted multiply-adds, which fuse into one pass over the
three streams (no ``[T, T]`` operator, no ``[T, L, H]`` window tensor). The
gates and the taps' sum are float32 whatever the streams' dtype: the pass is
bound by the bytes of its four ``[B, T, H]`` arrays, not by its arithmetic.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["gated_short_conv"]


def gated_short_conv(b, c, u, w):
    """b, c, u ``[B, T, H]``; w ``[H, L]``. Returns ``[B, T, H]`` in b's dtype."""
    taps = w.shape[-1]
    t = b.shape[1]
    w = w.astype(jnp.float32)
    a = b.astype(jnp.float32) * u.astype(jnp.float32)
    m = a * w[:, taps - 1]
    for j in range(taps - 1):
        back = taps - 1 - j             # tap j reads the position `back` before t
        m = m + jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :t] * w[:, j]
    return (c.astype(jnp.float32) * m).astype(b.dtype)
