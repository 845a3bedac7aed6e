"""One chip's share of a mixture of experts, dropless: the gated feed-forward
of the experts held here, applied to the (token, choice) pairs the router sent
to them, whatever the imbalance.

Plain XLA. The held pairs are laid out expert by expert in row blocks of
``block_rows`` (an expert's last block padded), and a loop runs over the
blocks that hold pairs: three products a block with that block's expert. The
buffers are sized for the worst case (every pair of every token held), the
loop's trip count is the routing's own, so the work follows the routed pairs
and no pair is ever dropped. The loop has a data-dependent length, so the
gradient is written out (``jax.custom_vjp``): the same loop again, with the
weight gradients accumulated in float32. Every move between tokens and rows
is a gather in both directions (pair -> row and row -> pair are both kept).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["expert_share_ffn"]

F32 = jnp.float32


def _layout(local_expert, n_held: int, block_rows: int):
    """Where each (token, choice) pair goes. ``local_expert`` ``[S, k]``: the
    pair's expert counted from the first held one (outside ``[0, n_held)``:
    not held). Returns the row of every pair (``n_rows`` where not held), the
    pair of every row (``S*k`` where the row is padding), each block's expert,
    the number of blocks in use and the held experts' pair counts."""
    s, k = local_expert.shape
    pairs = s * k
    n_blocks = -(-pairs // block_rows) + n_held
    n_rows = n_blocks * block_rows
    flat = local_expert.reshape(pairs)
    held = (flat >= 0) & (flat < n_held)
    group = jnp.where(held, flat, n_held)
    onehot = jax.nn.one_hot(group, n_held + 1, dtype=jnp.int32)
    counts = jnp.sum(onehot, axis=0)[:n_held]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    blocks_of = -(-counts // block_rows)
    block_end = jnp.cumsum(blocks_of)
    first_row = (block_end - blocks_of) * block_rows
    row_of_pair = jnp.where(
        held, first_row[jnp.minimum(group, n_held - 1)] + rank, n_rows)
    pair_of_row = jnp.full((n_rows,), pairs, jnp.int32).at[row_of_pair].set(
        jnp.arange(pairs, dtype=jnp.int32), mode="drop")
    block_expert = jnp.minimum(
        jnp.searchsorted(block_end, jnp.arange(n_blocks), side="right"),
        n_held - 1).astype(jnp.int32)
    return (row_of_pair.reshape(s, k), pair_of_row, block_expert,
            block_end[-1].astype(jnp.int32), counts)


def _block_inputs(x, gates, pair_of_row, i, block_rows: int, k: int):
    """Rows of block ``i``: their tokens' activations and their gates (0 on
    padding rows, whose token is read as token 0)."""
    pair = jax.lax.dynamic_slice(pair_of_row, (i * block_rows,), (block_rows,))
    real = pair < gates.size
    pair = jnp.where(real, pair, 0)
    token = pair // k
    gate = jnp.where(real, gates.reshape(-1)[pair], 0.0)
    return token, gate, x[token]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _share(act, block_rows, x, gates, w_gate, w_up, w_dn, layout):
    return _share_fwd(act, block_rows, x, gates, w_gate, w_up, w_dn, layout)[0]


def _share_fwd(act, block_rows, x, gates, w_gate, w_up, w_dn, layout):
    row_of_pair, pair_of_row, block_expert, n_used, _ = layout
    k = gates.shape[1]
    wg, wu, wd = (w.astype(x.dtype) for w in (w_gate, w_up, w_dn))

    def block(i, rows):
        _, _, xb = _block_inputs(x, gates, pair_of_row, i, block_rows, k)
        e = block_expert[i]
        hidden = act(jnp.dot(xb, wg[e], preferred_element_type=F32)) \
            * jnp.dot(xb, wu[e], preferred_element_type=F32)
        out = jnp.dot(hidden.astype(x.dtype), wd[e], preferred_element_type=F32)
        return jax.lax.dynamic_update_slice(rows, out.astype(x.dtype),
                                            (i * block_rows, 0))

    rows = jax.lax.fori_loop(
        0, n_used, block, jnp.zeros((pair_of_row.shape[0], x.shape[1]), x.dtype))
    # a pair that is not held reads row 0 under a gate of 0
    held = row_of_pair < rows.shape[0]
    at = jnp.where(held, row_of_pair, 0)
    y = jnp.zeros(x.shape, F32)
    for c in range(k):
        y = y + jnp.where(held[:, c], gates[:, c], 0.0)[:, None] * rows[at[:, c]].astype(F32)
    return y, (x, gates, w_gate, w_up, w_dn, layout)


def _share_bwd(act, block_rows, saved, dy):
    x, gates, w_gate, w_up, w_dn, layout = saved
    row_of_pair, pair_of_row, block_expert, n_used, _ = layout
    k = gates.shape[1]
    wg, wu, wd = (w.astype(x.dtype) for w in (w_gate, w_up, w_dn))
    dy = dy.astype(x.dtype)

    def block(i, carry):
        dx_rows, dgate_rows, dwg, dwu, dwd = carry
        token, gate, xb = _block_inputs(x, gates, pair_of_row, i, block_rows, k)
        e = block_expert[i]
        pre = jnp.dot(xb, wg[e], preferred_element_type=F32)
        up = jnp.dot(xb, wu[e], preferred_element_type=F32)
        acted, act_vjp = jax.vjp(act, pre)
        hidden = (acted * up).astype(x.dtype)
        out = jnp.dot(hidden, wd[e], preferred_element_type=F32)
        dyb = dy[token]
        dgate = jnp.sum(dyb.astype(F32) * out, axis=-1)
        dout = (gate[:, None] * dyb.astype(F32)).astype(x.dtype)
        dhidden = jnp.dot(dout, wd[e].T, preferred_element_type=F32)
        dpre = act_vjp(dhidden * up)[0].astype(x.dtype)
        dup = (dhidden * acted).astype(x.dtype)
        dxb = jnp.dot(dpre, wg[e].T, preferred_element_type=F32) \
            + jnp.dot(dup, wu[e].T, preferred_element_type=F32)
        add = lambda acc, a, b: acc.at[e].add(  # noqa: E731
            jnp.dot(a.T, b, preferred_element_type=F32))
        return (jax.lax.dynamic_update_slice(dx_rows, dxb.astype(x.dtype),
                                             (i * block_rows, 0)),
                jax.lax.dynamic_update_slice(dgate_rows, dgate, (i * block_rows,)),
                add(dwg, xb, dpre), add(dwu, xb, dup), add(dwd, hidden, dout))

    n_rows = pair_of_row.shape[0]
    dx_rows, dgate_rows, dwg, dwu, dwd = jax.lax.fori_loop(
        0, n_used, block,
        (jnp.zeros((n_rows, x.shape[1]), x.dtype), jnp.zeros((n_rows,), F32),
         jnp.zeros(w_gate.shape, F32), jnp.zeros(w_up.shape, F32),
         jnp.zeros(w_dn.shape, F32)))
    held = row_of_pair < n_rows
    at = jnp.where(held, row_of_pair, 0)
    dx = jnp.zeros(x.shape, F32)
    for c in range(k):
        dx = dx + jnp.where(held[:, c, None], dx_rows[at[:, c]].astype(F32), 0.0)
    dgates = jnp.where(held, dgate_rows[at], 0.0).astype(gates.dtype)
    return (dx.astype(x.dtype), dgates, dwg.astype(w_gate.dtype),
            dwu.astype(w_up.dtype), dwd.astype(w_dn.dtype), None)


_share.defvjp(_share_fwd, _share_bwd)


def expert_share_ffn(x, gates, experts, w_gate, w_up, w_dn, *, first_expert: int = 0,
                     act=jax.nn.silu, block_rows: int = 512):
    """``y[t] = sum over the choices c of token t whose expert is held here of
    gates[t, c] * (act(x[t] Wg_e) * (x[t] Wu_e)) Wd_e``, float32 ``[S, H]``.

    x ``[S, H]``; gates ``[S, k]`` float32 and experts ``[S, k]`` (ids over
    all the router's experts) as the router gives them; the held experts are
    ``first_expert .. first_expert + E - 1`` with weights w_gate, w_up
    ``[E, H, M]`` and w_dn ``[E, M, H]``. What experts held elsewhere would add
    is left out. Also returns the held experts' pair counts ``[E]``.
    """
    n_held = w_gate.shape[0]
    block_rows = int(min(block_rows, max(8, -(-gates.size // 8) * 8)))
    layout = jax.tree.map(jax.lax.stop_gradient, _layout(
        experts.astype(jnp.int32) - first_expert, n_held, block_rows))
    y = _share(act, block_rows, x, gates.astype(F32), w_gate, w_up, w_dn, layout)
    return y, layout[-1]
