"""Learned sparse attention: an indexer scores every causal key for every
query, the exact top-k of those scores is the query's key set, and softmax
attention runs over that set alone (DeepSeek sparse attention's "lightning
indexer"; the training path — a served path needs an indexer cache beside the
paged keys).

Plain XLA, static shapes. A query tile's scores against every key of its
segment are computed and masked: the mathematics and the gradients are those
of a gather of the selected keys, which at [T, top-k] keys a head does not
fit. Queries go in tiles of ``q_tile`` (a ``lax.scan`` whose body is
rematerialised, so one tile's scores are alive at a time) and in up to
``MAX_SEGMENTS`` segments, each against the keys up to its own end, so part of
the causal upper triangle is never computed (each segment is a loop of its own
in the compiled step: two keep three quarters of the square and the compile
time near a dense layer's).

What a step recomputes: the backward pass of a tile loop computes each tile's
scores a second time from the kept thresholds of its selection (a tile's
float32 scores, 537 MB at the benchmark's size, cannot be kept). A caller
that rematerialises the whole layer (``Encoder`` with ``cfg.remat``) keeps the
values named in ``REMAT_SAVED_NAMES``, the loops' output and those
thresholds, so that its re-run of the layer holds no tile loop: 134 MB a layer
at [2, 8192, 32, 128] bf16 against a third forward pass of the attention, a
fifth of the step (PERF.md, PR 30).

Device op names carry the scopes ``attn.indexer`` (index scores and the
indexer's loss), ``attn.select`` (the exact top-k) and ``attn.sparse`` (the
masked score, softmax and value products).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

__all__ = ["topk_mask", "indexed_attention", "REMAT_SAVED_NAMES"]

F32 = jnp.float32
MAX_SEGMENTS = 2
_THRESHOLD = "attn_select_threshold"
_OUT = "attn_indexed_out"
# what a rematerialised caller keeps in place of running the tile loops again
REMAT_SAVED_NAMES = (_OUT, _THRESHOLD)


def topk_mask(scores: jax.Array, candidates: jax.Array, k: int) -> jax.Array:
    """Exact top-k along the last axis as a mask: True at the
    ``min(k, #candidates)`` candidate positions with the largest score, equal
    scores taken lowest index first (``lax.top_k``'s order). No sort: the
    k-th largest score is found bit by bit (32 counting passes over a
    monotone integer image of the float32 scores), then the position of the
    last score taken among those equal to it (log2 of the row more).
    """
    x = jax.lax.stop_gradient(scores).astype(F32) + 0.0      # -0.0 -> +0.0
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    # monotone in x and above 0 for every number, so 0 is free for "no candidate"
    key = jnp.where(bits >> 31 == 0, bits | jnp.uint32(0x80000000), ~bits)
    key = jnp.where(candidates, key, jnp.uint32(0))

    def bit(i, kth):
        trial = kth | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= trial[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, kth)

    # the largest value that k keys reach: the k-th largest key, or 0 where a
    # row has fewer than k candidates (every candidate is then above it)
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:-1], jnp.uint32))
    kth = checkpoint_name(kth, _THRESHOLD)[..., None]
    above = key > kth
    tie = (key == kth) & candidates
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)      # at least 1
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
    last = checkpoint_name(last, _THRESHOLD)
    return above | (tie & (pos <= last[..., None]))


def _masked_softmax(x, mask, log: bool = False):
    """Softmax (or its log) of float32 ``x`` over the last axis, ``mask``'s
    positions alone (exactly 0 elsewhere). The row maximum passes an
    optimization barrier: without one XLA:TPU rewrites the broadcast of the
    maximum inside the rematerialised backward pass as a reduce-window as wide
    as the row, every element finding its row's maximum again (47 ms a tile
    of 256 queries x 8192 keys in place of under 1: PERF.md, PR 29)."""
    x = jnp.where(mask, x, jnp.finfo(F32).min)
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True)))
    shifted = x - top
    if log:
        return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    e = jnp.exp(shifted)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _segments(t: int, tile: int, topk: int) -> list[tuple[int, int]]:
    """[(first tile, tiles)] of each segment: segments of at least ``topk``
    queries (a shorter one saves nothing: its queries' key sets are whole),
    at most ``MAX_SEGMENTS`` of them."""
    n_tiles = -(-t // tile)
    per = max(-(-topk // tile), -(-n_tiles // MAX_SEGMENTS), 1)
    return [(a, min(per, n_tiles - a)) for a in range(0, n_tiles, per)]


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
    index_scale = 1.0 / math.sqrt(hi * di)
    pad = -t % tile
    if pad:
        q, qi, wi = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                     for a in (q, qi, wi))
    n_tiles = (t + pad) // tile
    # every product below is one batched [M, K] x [K, N]: batch dims merged
    # and leading, free dims merged (a product left with several batch and
    # free dims came out of XLA:TPU as a dilated convolution)
    q_t = q.reshape(b, n_tiles, tile, kv, g, d).transpose(1, 0, 3, 4, 2, 5) \
        .reshape(n_tiles, b * kv, g * tile, d)
    qi_t = jnp.moveaxis(qi.reshape(b, n_tiles, tile * hi, di), 1, 0)
    wi_t = jnp.moveaxis(wi.astype(F32).reshape(b, n_tiles, tile, hi), 1, 0)
    k_b = k.transpose(0, 2, 1, 3).reshape(b * kv, t, d)
    v_b = v.transpose(0, 2, 1, 3).reshape(b * kv, t, d)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile

    def one_tile(keys, _, xs):
        k_s, v_s, ki_s, mask_s = keys
        tq, tqi, twi, t0 = xs
        n_keys = k_s.shape[1]
        pos_q = t0 + jnp.arange(tile, dtype=jnp.int32)
        pos_k = jnp.arange(n_keys, dtype=jnp.int32)
        candidates = jnp.broadcast_to(pos_k[None, :] <= pos_q[:, None], (b, tile, n_keys))
        if mask_s is not None:
            candidates = candidates & mask_s[:, None, :]
        with jax.named_scope("attn.indexer"):
            head = jnp.einsum("bmd,bsd->bms", tqi, ki_s, preferred_element_type=F32)
            index = jnp.sum(jax.nn.relu(head).reshape(b, tile, hi, n_keys)
                            * twi[..., None], axis=2) * index_scale
        with jax.named_scope("attn.select"):
            chosen = topk_mask(index, candidates, topk)
        with jax.named_scope("attn.sparse"):
            scores = jnp.einsum("bmd,bsd->bms", tq, k_s,
                                preferred_element_type=F32) / math.sqrt(d)
            scores = scores.reshape(b, kv * g, tile, n_keys)
            probs = _masked_softmax(scores, chosen[:, None])
            out = jnp.einsum("bms,bsd->bmd",
                             probs.astype(v_s.dtype).reshape(b * kv, g * tile, n_keys), v_s)
        with jax.named_scope("attn.indexer"):
            target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
            log_index = _masked_softmax(index, chosen, log=True)
            live = chosen & (target > 0)
            kl = jnp.sum(jnp.where(
                live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_index),
                0.0), axis=-1)
        real = (pos_q < t)[None, :]
        return None, (out, jnp.sum(jnp.where(real, kl, 0.0)),
                      jnp.sum(jnp.where(real[..., None], chosen, False), dtype=F32),
                      jnp.sum(jnp.where(real[..., None], candidates, False), dtype=F32))

    outs, kl, n_chosen, n_candidates = [], 0.0, 0.0, 0.0
    for first, count in _segments(t, tile, topk):
        end = min((first + count) * tile, t)
        keys = (k_b[:, :end], v_b[:, :end], ki[:, :end],
                None if kv_mask is None else kv_mask[:, :end].astype(bool))
        # a tile's scores are recomputed in its backward pass; its selection's
        # threshold (one number a query) is kept instead of searched again
        body = jax.checkpoint(
            functools.partial(one_tile, keys),
            policy=jax.checkpoint_policies.save_only_these_names(_THRESHOLD))
        sl = slice(first, first + count)
        _, (o, a, c, n) = jax.lax.scan(body, None, (q_t[sl], qi_t[sl], wi_t[sl], starts[sl]))
        outs.append(o)
        kl, n_chosen, n_candidates = kl + jnp.sum(a), n_chosen + jnp.sum(c), \
            n_candidates + jnp.sum(n)
    # [tiles, B*KV, G*tile, D] -> [B, T, H, D]
    out = jnp.concatenate(outs, axis=0).reshape(n_tiles, b, kv, g, tile, d)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, t + pad, h, d)[:, :t]
    out = checkpoint_name(out, _OUT)
    return out, kl / (b * t), n_chosen / jnp.maximum(n_candidates, 1.0)
