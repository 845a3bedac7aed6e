"""Ring attention — sequence/context parallelism over a named mesh axis.

Net-new capability (SURVEY.md §5 "Long-context / sequence parallelism:
absent" in the reference): sequences longer than one chip's HBM are sharded
over the ``seq`` mesh axis; each device holds a Q/K/V shard, and K/V blocks
rotate around the ring via ``jax.lax.ppermute`` (ICI neighbor exchange) while
a running softmax accumulates the local contribution.

Memory/compile properties (long-context hardening):
  * the ring loop is ROLLED (``lax.fori_loop``) — compile size is independent
    of the ring length;
  * the inner block attention is CHUNKED (``lax.scan`` over K/V chunks with a
    running max/denominator) — no ``[T_loc, T_loc]`` score materialization;
    peak per-device live scores are ``[B, H, T_loc, chunk]``;
  * backward is a CUSTOM VJP that saves only (out, lse) and recomputes
    probabilities per ring step (flash-attention-style two-pass), with dK/dV
    accumulators traveling around the ring back to their owner shard.

``ring_attention`` is the collective form, called INSIDE ``jax.shard_map``
with per-device shards. ``ring_attention_sharded`` wraps full arrays for
callers holding a :class:`~synapseml_tpu.parallel.MeshContext`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def _pick_chunk(t_local: int, chunk: int) -> int:
    """Largest divisor of t_local that is <= chunk (static shapes for scan)."""
    c = min(chunk, t_local)
    while t_local % c:
        c -= 1
    return max(c, 1)


def _block_fwd(q, q_pos, k_blk, v_blk, mask_blk, kv_pos0, causal, m, l, acc,
               chunk):
    """Fold one K/V block into the running softmax, scanning over chunks.

    q/k_blk/v_blk: [B, T, H, D] in the INPUT dtype — the einsums run in that
    dtype (bf16 on the training path keeps the MXU off its ~4x slower f32
    path) with f32 accumulation; the softmax statistics are f32.
    mask_blk: [B, T] bool; kv_pos0: scalar global position of the block's
    first row. m, l: [B, H, T]; acc: [B, H, T, D] (f32). Returns (m, l, acc).
    """
    B, T, H, D = q.shape
    C = _pick_chunk(T, chunk)
    n_chunks = T // C
    scale = 1.0 / np.sqrt(D)

    def body(carry, c_idx):
        m, l, acc = carry
        start = c_idx * C
        ks = jax.lax.dynamic_slice_in_dim(k_blk, start, C, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v_blk, start, C, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask_blk, start, C, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ks,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(ms[:, None, None, :], scores, _NEG_INF)
        if causal:
            kv_pos = kv_pos0 + start + jnp.arange(C)
            allowed = kv_pos[None, :] <= q_pos[:, None]        # [T, C]
            scores = jnp.where(allowed[None, None], scores, _NEG_INF)
        blk_max = jnp.max(scores, axis=-1)                     # [B, H, T]
        new_m = jnp.maximum(m, blk_max)
        alpha = jnp.exp(m - new_m)
        # gated: fully-masked rows keep p == 0 (zero output, zero gradient)
        p = jnp.where(scores <= _NEG_INF * 0.5, 0.0,
                      jnp.exp(scores - new_m[..., None]))      # [B, H, T, C]
        new_l = l * alpha + jnp.sum(p, axis=-1)
        new_acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32)
        return (new_m, new_l, new_acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), jnp.arange(n_chunks))
    return m, l, acc


def _block_bwd(q, q_pos, k_blk, v_blk, mask_blk, kv_pos0, causal, lse, do,
               delta, dq, dk_blk, dv_blk, chunk):
    """Backward for one visiting K/V block: accumulate local dq and the
    block's traveling dk/dv. Matmul operands stay in the input dtype with f32
    accumulation; probability/score statistics and the dq/dk/dv accumulators
    are f32. lse: [B, H, T]; do: [B, H, T, D] (input dtype);
    delta: [B, H, T] (f32 sum(do * out)). Returns (dq, dk_blk, dv_blk)."""
    B, T, H, D = q.shape
    C = _pick_chunk(T, chunk)
    n_chunks = T // C
    scale = 1.0 / np.sqrt(D)

    def body(carry, c_idx):
        dq, dk_blk, dv_blk = carry
        start = c_idx * C
        ks = jax.lax.dynamic_slice_in_dim(k_blk, start, C, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v_blk, start, C, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask_blk, start, C, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ks,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(ms[:, None, None, :], scores, _NEG_INF)
        if causal:
            kv_pos = kv_pos0 + start + jnp.arange(C)
            allowed = kv_pos[None, :] <= q_pos[:, None]
            scores = jnp.where(allowed[None, None], scores, _NEG_INF)
        p = jnp.where(scores <= _NEG_INF * 0.5, 0.0,
                      jnp.exp(scores - lse[..., None]))        # [B, H, T, C]
        dv_c = jnp.einsum("bhqk,bhqd->bkhd", p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, vs,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])                       # [B, H, T, C]
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds.astype(ks.dtype), ks,
                             preferred_element_type=jnp.float32) * scale
        dk_c = jnp.einsum("bhqk,bqhd->bkhd", ds.astype(q.dtype), q,
                          preferred_element_type=jnp.float32) * scale
        dk_blk = jax.lax.dynamic_update_slice_in_dim(
            dk_blk, jax.lax.dynamic_slice_in_dim(dk_blk, start, C, 1) + dk_c,
            start, axis=1)
        dv_blk = jax.lax.dynamic_update_slice_in_dim(
            dv_blk, jax.lax.dynamic_slice_in_dim(dv_blk, start, C, 1) + dv_c,
            start, axis=1)
        return (dq, dk_blk, dv_blk), None

    (dq, dk_blk, dv_blk), _ = jax.lax.scan(body, (dq, dk_blk, dv_blk),
                                           jnp.arange(n_chunks))
    return dq, dk_blk, dv_blk


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_core(q, k, v, kv_mask, axis_name, axis_size, causal, chunk):
    out, _ = _ring_fwd_impl(q, k, v, kv_mask, axis_name, axis_size, causal, chunk)
    return out


def _ring_fwd_impl(q, k, v, kv_mask, axis_name, axis_size, causal, chunk):
    B, T, H, D = q.shape
    my = jax.lax.axis_index(axis_name)
    qf = q.astype(jnp.float32)
    q_pos = my * T + jnp.arange(T)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(s, carry):
        k_cur, v_cur, mask_cur, m, l, acc = carry
        origin = (my - s) % axis_size
        m, l, acc = _block_fwd(q, q_pos, k_cur, v_cur, mask_cur, origin * T,
                               causal, m, l, acc, chunk)
        # rotate K/V/mask to the next device; the final rotation restores the
        # original residency and keeps the loop body uniform
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        return k_nxt, v_nxt, mask_nxt, m, l, acc

    # derive accumulators from q so they carry the same shard_map
    # varying-axes type as the loop outputs (check_vma)
    zeros_bht = jnp.transpose(jnp.sum(qf, axis=-1) * 0.0, (0, 2, 1))
    m0 = zeros_bht + _NEG_INF
    l0 = zeros_bht
    acc0 = jnp.transpose(qf * 0.0, (0, 2, 1, 3))
    carry = (k, v, kv_mask, m0, l0, acc0)
    carry = jax.lax.fori_loop(0, axis_size, step, carry)
    _, _, _, m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]               # [B, H, T, D]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))                   # [B, H, T]
    out_bthd = jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
    return out_bthd, (out, lse)


def _ring_core_fwd(q, k, v, kv_mask, axis_name, axis_size, causal, chunk):
    out_bthd, (out_f32, lse) = _ring_fwd_impl(q, k, v, kv_mask, axis_name,
                                              axis_size, causal, chunk)
    return out_bthd, (q, k, v, kv_mask, out_f32, lse)


def _ring_core_bwd(axis_name, axis_size, causal, chunk, res, g):
    q, k, v, kv_mask, out, lse = res
    B, T, H, D = q.shape
    my = jax.lax.axis_index(axis_name)
    qf = q.astype(jnp.float32)
    q_pos = my * T + jnp.arange(T)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    do = jnp.transpose(g, (0, 2, 1, 3)).astype(q.dtype)        # [B, H, T, D]
    # re-apply the softmax-normalization jacobian piece: out = acc / l and
    # d(acc/l) folds into ds via delta = sum(do * out)
    delta = jnp.sum(do.astype(jnp.float32) * out, axis=-1)     # [B, H, T]

    def step(s, carry):
        k_cur, v_cur, mask_cur, dk_cur, dv_cur, dq = carry
        origin = (my - s) % axis_size
        dq, dk_cur, dv_cur = _block_bwd(
            q, q_pos, k_cur, v_cur,
            mask_cur, origin * T, causal, lse, do, delta, dq, dk_cur, dv_cur,
            chunk)
        # dk/dv travel WITH their block so every shard adds its contribution;
        # after axis_size rotations they are back at the owner
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
        return k_nxt, v_nxt, mask_nxt, dk_nxt, dv_nxt, dq

    dk0 = qf * 0.0
    dv0 = qf * 0.0
    dq0 = qf * 0.0
    carry = (k, v, kv_mask, dk0, dv0, dq0)
    _, _, _, dk, dv, dq = jax.lax.fori_loop(0, axis_size, step, carry)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention(q, k, v, axis_name: str, axis_size: int, kv_mask=None,
                   causal: bool = False, chunk: int = 512):
    """Blockwise ring attention over ``axis_name``; call inside ``shard_map``.

    Args:
      q, k, v: local shards ``[B, T_local, H, D]`` (equal-length shards; global
        position of row t on shard i is ``i * T_local + t``).
      axis_name: mesh axis carrying the sequence dimension.
      axis_size: static size of that axis (ring length).
      kv_mask: optional ``[B, T_local]`` bool for the local K/V shard.
      causal: apply a global causal mask built from shard offsets.
      chunk: inner K/V chunk size bounding live score memory to
        ``[B, H, T_local, chunk]``.

    Fully-masked query rows yield zeros. Accumulation is float32;
    differentiable via a recompute-per-ring-step custom VJP.
    """
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], bool)
    return _ring_core(q, k, v, kv_mask, axis_name, axis_size, causal, chunk)


def _mesh_of(mesh_like):
    """Accept a MeshContext, a jax Mesh, or an AbstractMesh."""
    mesh = getattr(mesh_like, "mesh", mesh_like)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes
                     if hasattr(mesh, "axis_sizes") else mesh.devices.shape))
    return mesh, sizes


def seq_parallel_shard_map(mesh_ctx, q, k, v, kv_mask, causal, seq_axis,
                           batch_axes, head_axis, fn_factory,
                           head_needs_seq_factor: bool = False,
                           check_vma: bool = True):
    """Shared full-array wrapper for the sequence-parallel strategies.

    Resolves the mesh, falls back to plain attention when the seq axis is
    absent/size-1, builds the batch/seq/head PartitionSpecs (the head axis is
    used only when the head count divides its sharding — times the seq size
    too when ``head_needs_seq_factor``, as Ulysses splits heads across the
    seq axis as well), and shard_maps ``fn_factory(axis_size)`` which must
    return a per-shard ``fn(q, k, v, kv_mask)``.
    """
    from jax.sharding import PartitionSpec as P

    mesh, sizes = _mesh_of(mesh_ctx)
    n = sizes.get(seq_axis, 1)
    H = q.shape[2]
    batch_axes = tuple(a for a in batch_axes if a in sizes)
    divisor = max(sizes.get(head_axis, 1), 1) * (n if head_needs_seq_factor else 1)
    head = (head_axis if head_axis and head_axis in sizes
            and H % divisor == 0 else None)
    if n <= 1:
        from .attention import reference_attention
        return reference_attention(q, k, v, kv_mask=kv_mask, causal=causal)
    qkv_spec = P(batch_axes or None, seq_axis, head, None)
    mask_spec = P(batch_axes or None, seq_axis)
    fn = fn_factory(n)
    mapped = jax.shard_map(
        lambda q_, k_, v_, m_: fn(q_, k_, v_, kv_mask=m_),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        check_vma=check_vma)
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], bool)
    return mapped(q, k, v, kv_mask)


def ring_attention_sharded(mesh_ctx, q, k, v, kv_mask=None, causal: bool = False,
                           seq_axis: str = "seq", batch_axes=("data", "fsdp"),
                           head_axis: str | None = "tensor", chunk: int = 512):
    """Full-array entry point: shard_map ``ring_attention`` over the mesh.

    q, k, v: ``[B, T, H, D]`` global arrays (T divisible by the seq-axis size).
    ``mesh_ctx`` may be a :class:`~synapseml_tpu.parallel.MeshContext`, a
    ``jax.sharding.Mesh``, or an ``AbstractMesh``.
    """
    return seq_parallel_shard_map(
        mesh_ctx, q, k, v, kv_mask, causal, seq_axis, batch_axes, head_axis,
        lambda n: functools.partial(ring_attention, axis_name=seq_axis,
                                    axis_size=n, causal=causal, chunk=chunk))
