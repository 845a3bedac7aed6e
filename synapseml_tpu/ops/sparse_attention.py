"""Learned sparse attention: an indexer scores every causal key for every
query, the exact top-k of those scores is the query's key set, and softmax
attention runs over that set alone (DeepSeek sparse attention's "lightning
indexer"; the training path — a served path needs an indexer cache beside the
paged keys).

Plain XLA, static shapes. A query tile's scores against every key of its
segment are computed and masked: the mathematics and the gradients are those
of a gather of the selected keys, which at [T, top-k] keys a head does not
fit. Queries go in tiles of ``q_tile`` (a ``lax.scan`` that keeps no tile's
scores, so one tile's are alive at a time, in either pass) and in up to
``MAX_SEGMENTS`` segments, each against the keys up to its own end, so part of
the causal upper triangle is never computed (each segment is a loop of its own
in the compiled step: two keep three quarters of the square and the compile
time near a dense layer's).

What a step recomputes: a tile's backward pass is written by hand
(``jax.custom_vjp`` on the tile). The forward pass keeps, beside the tile's
inputs, its output, the two thresholds of its selection and two row
statistics: for each query the log of the sum over its key set of
``exp(score)``, one a head, and of ``exp(index score)`` (a tile's float32
scores, 537 MB at the benchmark's size, cannot be kept). The backward pass
computes the indexer's head products, the index scores and the attention
scores a second time and forms the probabilities from the kept statistics in
one pass: no row maximum, no row sum, no search. The score gradient and the
indexer's head gradient leave their fusions in the keys' dtype, which is what
the MXU would round a float32 operand to. A caller that rematerialises the whole
layer (``Encoder`` with ``cfg.remat``) keeps the values named in
``REMAT_SAVED_NAMES``, exactly those residuals, so that its re-run of the
layer holds no tile loop: 136 MB a layer at [2, 8192, 32, 128] bf16 against a
third forward pass of the attention, a fifth of the step (PERF.md, PRs 30 and
32).

Device op names carry the scopes ``attn.indexer`` (index scores and the
indexer's loss), ``attn.select`` (the exact top-k) and ``attn.sparse`` (the
masked score, softmax and value products).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

__all__ = ["topk_mask", "indexed_attention", "REMAT_SAVED_NAMES"]

F32 = jnp.float32
MAX_SEGMENTS = 2
_THRESHOLD = "attn_select_threshold"
_OUT = "attn_indexed_out"
_ROW_STAT = "attn_indexed_row_stat"
# what a tile's backward pass reads beside the tile's inputs, and so what a
# rematerialised caller keeps in place of running the tile loops again
REMAT_SAVED_NAMES = (_OUT, _THRESHOLD, _ROW_STAT)


def _sort_key(scores: jax.Array, candidates: jax.Array) -> jax.Array:
    """A uint32 image of the float32 scores that orders as they do, above 0
    for every number: 0 is free for "no candidate"."""
    x = jax.lax.stop_gradient(scores).astype(F32) + 0.0      # -0.0 -> +0.0
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    key = jnp.where(bits >> 31 == 0, bits | jnp.uint32(0x80000000), ~bits)
    return jnp.where(candidates, key, jnp.uint32(0))


def _find_thresholds(key: jax.Array, candidates: jax.Array, k: int):
    """``(kth, last)`` of each row of ``key``: the k-th largest key, found bit
    by bit (32 counting passes), and the position of the last key taken among
    those equal to it (log2 of the row more). No sort."""

    def bit(i, kth):
        trial = kth | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= trial[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, kth)

    # the largest value that k keys reach: the k-th largest key, or 0 where a
    # row has fewer than k candidates (every candidate is then above it)
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:-1], jnp.uint32))
    tie = (key == kth[..., None]) & candidates
    need = k - jnp.sum(key > kth[..., None], axis=-1, dtype=jnp.int32)   # at least 1
    # the position of the need-th tie, found the same way: the largest x with
    # fewer than `need` ties before it (no running sum over the row: XLA:TPU's
    # costs the row's length squared)
    pos = jnp.arange(key.shape[-1], dtype=jnp.int32)
    n_bits = max(int(key.shape[-1]).bit_length(), 1)

    def place(i, x):
        trial = x | (jnp.int32(1 << (n_bits - 1)) >> i)
        before = jnp.sum(tie & (pos < trial[..., None]), axis=-1, dtype=jnp.int32)
        return jnp.where(before < need, trial, x)

    last = jax.lax.fori_loop(0, n_bits, place, jnp.zeros(key.shape[:-1], jnp.int32))
    return kth, last


def _mask_from_thresholds(key, candidates, kth, last) -> jax.Array:
    pos = jnp.arange(key.shape[-1], dtype=jnp.int32)
    tie = (key == kth[..., None]) & candidates
    return (key > kth[..., None]) | (tie & (pos <= last[..., None]))


def topk_mask(scores: jax.Array, candidates: jax.Array, k: int) -> jax.Array:
    """Exact top-k along the last axis as a mask: True at the
    ``min(k, #candidates)`` candidate positions with the largest score, equal
    scores taken lowest index first (``lax.top_k``'s order)."""
    key = _sort_key(scores, candidates)
    return _mask_from_thresholds(key, candidates, *_find_thresholds(key, candidates, k))


def _masked_softmax(x, mask, log: bool = False):
    """``(softmax, L)`` of float32 ``x`` over the last axis, ``mask``'s
    positions alone (exactly 0 elsewhere), or the softmax's log: ``L`` is the
    log of the row's sum of ``exp(x)`` over the mask, so that the softmax is
    ``exp(x - L)`` there. The row maximum passes an optimization barrier:
    without one XLA:TPU rewrites the broadcast of the maximum inside a
    rematerialised backward pass as a reduce-window as wide as the row, every
    element finding its row's maximum again (47 ms a tile of 256 queries x
    8192 keys in place of under 1: PERF.md, PR 29)."""
    x = jnp.where(mask, x, jnp.finfo(F32).min)
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True)))
    shifted = x - top
    e = jnp.exp(shifted)
    total = jnp.sum(e, axis=-1, keepdims=True)
    return (shifted - jnp.log(total) if log else e / total), top + jnp.log(total)


def _segments(t: int, tile: int, topk: int) -> list[tuple[int, int]]:
    """[(first tile, tiles)] of each segment: segments of at least ``topk``
    queries (a shorter one saves nothing: its queries' key sets are whole),
    at most ``MAX_SEGMENTS`` of them."""
    n_tiles = -(-t // tile)
    per = max(-(-topk // tile), -(-n_tiles // MAX_SEGMENTS), 1)
    return [(a, min(per, n_tiles - a)) for a in range(0, n_tiles, per)]


class _Tile(NamedTuple):
    """What every tile of a call shares: batch, key heads, query heads a key
    head, queries a tile, indexer heads and their width, the real row length
    and top-k."""

    b: int
    kv: int
    g: int
    tile: int
    hi: int
    di: int
    t: int
    topk: int

    @property
    def index_scale(self) -> float:
        return 1.0 / math.sqrt(self.hi * self.di)


def _tile_scores(dims: _Tile, tq, tqi, twi, t0, k_s, ki_s, mask_s):
    """A tile's candidates, the indexer's head products after their ReLU, index
    scores and attention scores, all float32 from here on: what both passes
    compute."""
    b, tile, n_keys = dims.b, dims.tile, k_s.shape[1]
    pos_q = t0 + jnp.arange(tile, dtype=jnp.int32)
    pos_k = jnp.arange(n_keys, dtype=jnp.int32)
    candidates = jnp.broadcast_to(pos_k[None, :] <= pos_q[:, None], (b, tile, n_keys))
    if mask_s is not None:
        candidates = candidates & mask_s[:, None, :]
    with jax.named_scope("attn.indexer"):
        head = jax.nn.relu(jnp.einsum("bmd,bsd->bms", tqi, ki_s, preferred_element_type=F32)
                           .reshape(b, tile, dims.hi, n_keys))
        index = jnp.sum(head * twi[..., None], axis=2) * dims.index_scale
    with jax.named_scope("attn.sparse"):
        scores = jnp.einsum("bmd,bsd->bms", tq, k_s,
                            preferred_element_type=F32) / math.sqrt(tq.shape[-1])
        scores = scores.reshape(b, dims.kv * dims.g, tile, n_keys)
    return candidates, pos_q < dims.t, head, index, scores


def _tile_forward(dims: _Tile, tq, tqi, twi, t0, k_s, v_s, ki_s, mask_s):
    """One tile of queries against the keys of its segment: ``(out, the
    indexer's loss summed over the real queries, keys chosen, candidates)``
    and what the backward pass needs of it, ``(kth, last, L, Li)``."""
    candidates, real, _, index, scores = _tile_scores(dims, tq, tqi, twi, t0, k_s, ki_s, mask_s)
    with jax.named_scope("attn.select"):
        key = _sort_key(index, candidates)
        kth, last = _find_thresholds(key, candidates, dims.topk)
        chosen = _mask_from_thresholds(key, candidates, kth, last)
    with jax.named_scope("attn.sparse"):
        probs, lse = _masked_softmax(scores, chosen[:, None])
        out = jnp.einsum("bms,bsd->bmd", probs.astype(v_s.dtype).reshape(
            dims.b * dims.kv, dims.g * dims.tile, -1), v_s)
    with jax.named_scope("attn.indexer"):
        target = jnp.mean(probs, axis=1)
        log_index, lse_index = _masked_softmax(index, chosen, log=True)
        live = chosen & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_index), 0.0), axis=-1)
    counts = (jnp.sum(jnp.where(real[:, None], x, False), dtype=F32)
              for x in (chosen, candidates))
    return (out, jnp.sum(jnp.where(real, kl, 0.0)), *counts), (kth, last, lse, lse_index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tile(dims: _Tile, tq, tqi, twi, t0, k_s, v_s, ki_s, mask_s):
    return _tile_forward(dims, tq, tqi, twi, t0, k_s, v_s, ki_s, mask_s)[0]


def _tile_fwd(dims: _Tile, *inputs):
    (out, *sums), (kth, last, lse, lse_index) = _tile_forward(dims, *inputs)
    # named here, in the loop, so that the stacked tiles are at once the
    # residual and the source of the layer's output
    out = checkpoint_name(out, _OUT)
    kept = (out, checkpoint_name(kth, _THRESHOLD), checkpoint_name(last, _THRESHOLD),
            checkpoint_name(lse, _ROW_STAT), checkpoint_name(lse_index, _ROW_STAT))
    return (out, *sums), (inputs, kept)


def _tile_bwd(dims: _Tile, residuals, cotangents):
    """The gradients of ``out`` and of the indexer's loss (none through the
    loss's target, the selection or the two counts) to the tile's queries, the
    indexer's queries and weights and the three key tensors."""
    (tq, tqi, twi, t0, k_s, v_s, ki_s, mask_s), (out, kth, last, lse, lse_index) = residuals
    d_out, d_kl = cotangents[:2]
    b, tile, n_keys = dims.b, dims.tile, k_s.shape[1]
    rows = (b * dims.kv, dims.g * tile, n_keys)
    candidates, real, head, index, scores = _tile_scores(dims, tq, tqi, twi, t0, k_s, ki_s, mask_s)
    with jax.named_scope("attn.select"):
        chosen = _mask_from_thresholds(_sort_key(index, candidates), candidates, kth, last)
    with jax.named_scope("attn.sparse"):
        probs = jnp.where(chosen[:, None], jnp.exp(scores - lse), 0.0)
        # sum over the keys of probs * d_probs, from the output: [.., D] not [.., keys]
        delta = jnp.sum(d_out.astype(F32) * out.astype(F32), axis=-1, keepdims=True)
        d_probs = jnp.einsum("bmd,bsd->bms", d_out, v_s, preferred_element_type=F32)
        # in the keys' dtype: what the MXU makes of a float32 operand
        d_scores = (probs.reshape(rows) * (d_probs - delta)
                    / math.sqrt(tq.shape[-1])).astype(k_s.dtype)
        d_q = jnp.einsum("bms,bsd->bmd", d_scores, k_s).astype(tq.dtype)
        d_k = jnp.einsum("bms,bmd->bsd", d_scores, tq).astype(k_s.dtype)
        d_v = jnp.einsum("bms,bmd->bsd", probs.astype(v_s.dtype).reshape(rows), d_out) \
            .astype(v_s.dtype)
    with jax.named_scope("attn.indexer"):
        target = jnp.mean(probs, axis=1)
        taken = jnp.where(chosen & (target > 0), target, 0.0)
        index_probs = jnp.where(chosen, jnp.exp(index - lse_index), 0.0)
        d_index = jnp.where(real, d_kl, 0.0)[:, None] * dims.index_scale * (
            index_probs * jnp.sum(taken, axis=-1, keepdims=True) - taken)
        d_index = d_index[:, :, None, :]
        # in the indexer keys' dtype likewise. `head` is past its ReLU, so its
        # sign is the ReLU's gate (for `head > 0` XLA:TPU compares the raw
        # products and writes a tile of them out a second time)
        d_head = (d_index * twi[..., None] * jnp.sign(head)).astype(ki_s.dtype) \
            .reshape(b, tile * dims.hi, n_keys)
        d_qi = jnp.einsum("bms,bsd->bmd", d_head, ki_s).astype(tqi.dtype)
        d_ki = jnp.einsum("bms,bmd->bsd", d_head, tqi).astype(ki_s.dtype)
        d_wi = jnp.sum(d_index * head, axis=-1)
    return d_q, d_qi, d_wi, None, d_k, d_v, d_ki, None


_tile.defvjp(_tile_fwd, _tile_bwd)


def indexed_attention(q, k, v, qi, ki, wi, *, topk: int, q_tile: int = 512,
                      kv_mask=None):
    """Causal attention of every query over its indexer-chosen keys.

    q ``[B, T, H, D]``; k, v ``[B, T, KV, D]`` (grouped queries: H a multiple
    of KV); indexer queries qi ``[B, T, HI, DI]``, its one key head ki
    ``[B, T, DI]`` and head weights wi ``[B, T, HI]``; ``kv_mask`` ``[B, T]``
    marks real keys. Index score of key s for query t:
    ``(HI*DI)**-0.5 * sum_j wi[t, j] * relu(qi[t, j] . ki[s])``, float32.

    Returns ``(out [B, T, H, D], kl, share)``: ``kl`` is the indexer's loss,
    the mean over queries of KL(p || softmax over the key set of the index
    scores) with p the heads' mean attention (no gradient through p, none
    through the selection), and ``share`` the selected share of the causal
    candidates.
    """
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    hi, di = qi.shape[2], qi.shape[3]
    tile = min(int(q_tile), t)
    pad = -t % tile
    if pad:
        q, qi, wi = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                     for a in (q, qi, wi))
    n_tiles = (t + pad) // tile
    # every product of a tile is one batched [M, K] x [K, N]: batch dims merged
    # and leading, free dims merged (a product left with several batch and
    # free dims came out of XLA:TPU as a dilated convolution)
    q_t = q.reshape(b, n_tiles, tile, kv, g, d).transpose(1, 0, 3, 4, 2, 5) \
        .reshape(n_tiles, b * kv, g * tile, d)
    qi_t = jnp.moveaxis(qi.reshape(b, n_tiles, tile * hi, di), 1, 0)
    wi_t = jnp.moveaxis(wi.astype(F32).reshape(b, n_tiles, tile, hi), 1, 0)
    k_b = k.transpose(0, 2, 1, 3).reshape(b * kv, t, d)
    v_b = v.transpose(0, 2, 1, 3).reshape(b * kv, t, d)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile
    dims = _Tile(b=b, kv=kv, g=g, tile=tile, hi=hi, di=di, t=t, topk=topk)

    outs, kl, n_chosen, n_candidates = [], 0.0, 0.0, 0.0
    for first, count in _segments(t, tile, topk):
        end = min((first + count) * tile, t)
        keys = (k_b[:, :end], v_b[:, :end], ki[:, :end],
                None if kv_mask is None else kv_mask[:, :end].astype(bool))
        sl = slice(first, first + count)
        _, (o, a, c, n) = jax.lax.scan(
            lambda _, xs, keys=keys: (None, _tile(dims, *xs, *keys)),
            None, (q_t[sl], qi_t[sl], wi_t[sl], starts[sl]))
        outs.append(o)
        kl, n_chosen, n_candidates = kl + jnp.sum(a), n_chosen + jnp.sum(c), \
            n_candidates + jnp.sum(n)
    # [tiles, B*KV, G*tile, D] -> [B, T, H, D]
    out = jnp.concatenate(outs, axis=0).reshape(n_tiles, b, kv, g, tile, d)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, t + pad, h, d)[:, :t]
    return out, kl / (b * t), n_chosen / jnp.maximum(n_candidates, 1.0)
