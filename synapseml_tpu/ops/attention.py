"""Blockwise (flash) attention — Pallas TPU kernel + XLA fallback.

The reference's attention hot loop lives inside torch CUDA kernels reached via
``dl/LitDeepTextModel.py`` / ONNX Runtime (SURVEY.md §2.3); the TPU-native
equivalent is a fused Pallas kernel: Q/K/V stream HBM→VMEM in blocks, the
running-softmax (max/sum) accumulators stay in VMEM scratch, and only the
normalized output is written back — O(T) memory instead of materializing the
[T, T] score matrix.

Layout contract: ``q, k: [B, T, H, D]``, ``v: [B, T, H, Dv]`` (same as
:mod:`models.flax_nets`; ``Dv`` may differ from ``D``: latent attention's keys
are wider than its values), ``kv_mask: [B, T]`` boolean (True = attend).
Fully-masked query rows output
exactly zero (same contract as :func:`reference_attention` and ring
attention) — padding rows carry no gradient and are sliced away downstream.

Two variants of the forward kernel, chosen from what the call passes (counted
in ``synapseml_flash_kernel_builds_total{variant}``, once a trace of the op):
``unmasked`` when ``kv_mask`` is None and the keys are a whole number of key
blocks of whole 128-lane tiles, ``masked`` for every other call. The unmasked
one has no mask operand and no masked-row guard: under a causal mask every
row's first block holds key 0, so the running maximum is finite before any
``-1e30`` entry meets it and ``exp`` gives an exact 0; without ``causal`` no
entry is masked at all. Both compute the same numbers wherever both apply.

A sliding window (``window`` > 0, causal only: query ``i`` sees keys ``i -
window < j <= i``, itself and the ``window - 1`` before it) runs the same two
kernels under a scope of its own, ``attn.window`` (not nested in
``attn.flash``), counted as ``variant="window"``, and visits only the (query
block, key block) pairs that meet the band, forward and backward; the band's
edge tiles are masked inside the kernel. The unmasked variant's premise does
not hold there (a row's band may miss a whole key block), so its step tables
list each query block's key blocks from the diagonal BACKWARDS: a row's own
key is in the first tile it meets, its running maximum is finite from the
first step, and no masked-row guard is needed. The masked variant keeps its
guard and its order. While tracing, a windowed call sets the gauge
``synapseml_attn_window_pairs_ratio``: the pairs its tiles compute (tiles x
block_q x block_k) over the band's pairs.

Backward pass: a custom VJP recomputes attention blockwise in XLA from the
saved log-sum-exp — no [T, T] materialization, no second Pallas kernel needed.
It walks the (key block, query block) pairs that hold work once (under a
causal mask those on and below the diagonal: the others hold no pair and would
add exact zeros): a pair forms its score, probability and gradient tiles one
time and feeds ``dq``, ``dk`` and ``dv`` from them, five products where a loop
nest for ``dq`` and a second one for ``dk`` and ``dv`` ran seven (PR 38). The
key block's ``dk`` and ``dv`` ride in the inner loop's carry; ``dq`` is a
float32 buffer of query blocks, and each pair reads its block, adds to it and
writes it back through HBM inside the product's own fusion: ``[32, 512, 128]``
float32 = 8.4 MB each way a pair for 32 heads of 64 over 32,768 positions
(2,080 pairs of 512-blocks), ``[32, 512, 256]`` = 16.8 MB for two rows of 16
heads 192 wide over 8,192 (136 pairs a layer). The buffer (537 MB; 268 MB)
lives beside ``dk`` and ``dv`` for the length of the pass. The pair's body
follows the call as the forward kernel's does: without a key mask
(``unmasked``) it selects on no mask block, builds no ``[BH, block_q,
block_k]`` mask tile and guards no masked row (``exp`` of ``-1e30`` less a
finite log-sum-exp is an exact 0). On a v5e, inside the two cells' steps, the
pass takes ~207 ms at the first shape where the two loop nests took ~257, and
~122 ms over five layers against ~143 at the second (:func:`_flash_core_bwd`
has the microseconds a pair); every sum keeps its order, so the float32
gradients are the two loop nests' bit for bit (PERF.md section 6, PR 38).

What a rematerialising caller keeps: beside the op's inputs the backward pass
reads the kernel's output and log-sum-exp, which the forward rule names
(``REMAT_SAVED_NAMES``). A caller that rematerialises the whole layer
(``Encoder`` with ``cfg.remat``) keeps those two by name, so its re-run of the
layer reads them and holds no kernel: one launch a step, not two. It re-runs
what makes ``q``, ``k`` and ``v`` (norm, projections, RoPE, pads), which are
not named. Kept, as the kernel wrote them (widths padded to 128 lanes, the
log-sum-exp float32): ``[32, 32768, 128]`` bf16 = 268 MB + 4 MB for one layer
of 32 heads of 64 over 32,768 positions, where a launch takes 70.0 ms on a
v5e; ``[32, 8192, 128]`` bf16 = 67 MB + 1 MB a layer for two rows of 16 heads
at 192 / 128 over 8,192, where it takes 6.3 ms. The step's peak memory does
not follow these bytes: with the output a residual XLA:TPU lays the rest of
the step out anew (13.63 -> 13.60 GB in the first case; 10.70 -> 14.82 GB in
the second, five layers, where it puts every weight gradient's product after
the last layer's backward pass: PERF.md section 6, PR 36). Outside such a
caller the names are identities.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..core import observability as obs
from ..core import platform

_NEG_INF = -1e30
_OUT = "attn_flash_out"
_LSE = "attn_flash_lse"
# what the backward pass reads beside the op's inputs, and so what a
# rematerialised caller keeps in place of launching the kernel again
REMAT_SAVED_NAMES = (_OUT, _LSE)


def reference_attention(q, k, v, kv_mask=None, causal: bool = False,
                        q_offset=0, kv_offset=0, window: int = 0):
    """Plain XLA attention (the correctness oracle). [B,T,H,D] layout.

    ``q_offset``/``kv_offset`` are global position offsets so sequence-parallel
    shards can build the right causal mask (used by ring attention).
    ``window`` > 0 (with ``causal``) keeps the keys ``q - window < kv <= q``.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(D)
    if causal:
        q_pos = q_offset + jnp.arange(Tq)[:, None]
        kv_pos = kv_offset + jnp.arange(Tk)[None, :]
        scores = jnp.where((kv_pos <= q_pos)[None, None], scores, _NEG_INF)
        if window:
            scores = jnp.where((kv_pos > q_pos - window)[None, None], scores, _NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :], scores, _NEG_INF)
    any_valid = jnp.any(scores > _NEG_INF * 0.5, axis=-1)        # [B,H,Tq]
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(any_valid[..., None], probs, 0.0)          # zero masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *,
                      block_k: int, n_kblocks: int, scale: float, causal: bool,
                      block_q: int, window: int):
    """One (batch*head, q-block, kv-block) program. Only ONE block_k-sized K/V
    tile is VMEM-resident at a time (streamed by the grid's innermost
    dimension); the running max/sum/accumulator live in VMEM scratch that
    persists across the kv dimension and is written out on the last step.
    Under a ``window`` the steps before the band's first key block compute
    nothing, as those above the diagonal."""
    from jax.experimental import pallas as pl

    q_blk = pl.program_id(1)
    kv_blk = pl.program_id(2)

    @pl.when(kv_blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        # dots stay in the INPUT dtype (bf16 on the training path) with f32
        # accumulation — a pre-cast to f32 would push the MXU onto its ~4x
        # slower f32 path; only the softmax statistics need f32. The scale is
        # applied post-dot in f32 (no bf16 rounding of q, no padded-D fixup).
        q = q_ref[0]                                    # [block_q, D]
        k_blk = k_ref[0]                                # [block_k, D]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = mask_ref[0, 0] != 0                     # [bk]
        s = jnp.where(valid[None, :], s, _NEG_INF)
        if causal:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kv_pos = kv_blk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kv_pos <= q_pos, s, _NEG_INF)
            if window:
                s = jnp.where(kv_pos > q_pos - window, s, _NEG_INF)
        m = m_scr[:, 0]
        new_m = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - new_m)
        # gate, not just subtract: for fully-masked rows s == new_m == -1e30
        # and exp(0) would count masked entries (f32 absorbs log(l) into -1e30)
        p = jnp.where(s <= _NEG_INF * 0.5, 0.0, jnp.exp(s - new_m[:, None]))
        l_scr[...] = (l_scr[...] * alpha[:, None]
                      + jnp.broadcast_to(jnp.sum(p, axis=1)[:, None], l_scr.shape))
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(new_m[:, None], m_scr.shape)

    if window:
        # skip kv blocks fully above the diagonal or before the band
        pl.when((kv_blk <= _diagonal_kv_block(q_blk, block_q, block_k))
                & (kv_blk >= _band_first_kv_block(q_blk, block_q, block_k, window)))(_compute)
    elif causal:
        # skip kv blocks fully above the diagonal
        pl.when(kv_blk * block_k <= (q_blk + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kv_blk == n_kblocks - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[...] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_scr[:, 0] + jnp.log(safe_l)


def _diagonal_kv_block(q_blk, block_q: int, block_k: int):
    """The key block that holds the position of query block ``q_blk``'s last
    row: under a causal mask the last one it needs (an int, an array of them
    or a traced scalar)."""
    return ((q_blk + 1) * block_q - 1) // block_k


def _band_first_kv_block(q_blk, block_q: int, block_k: int, window: int):
    """The key block that holds the first key query block ``q_blk``'s first
    row sees under a window: the first one it needs (negative where the band
    reaches before key 0; an int, an array of them or a traced scalar)."""
    return (q_blk * block_q - window + 1) // block_k


def _flash_fwd_kernel_unmasked(q_blk_ref, kv_blk_ref, q_ref, k_ref, v_ref, o_ref,
                               lse_ref, m_scr, l_scr, acc_scr, *,
                               block_q: int, block_k: int, n_kblocks: int,
                               scale: float, causal: bool, window: int):
    """One (batch*head, step) program of a call without a key mask. The step's
    query and key block come from the two scalar-prefetched tables, which list
    the pairs that hold work in row order (under ``causal`` the lower triangle:
    no step is spent and no block fetched above the diagonal). Differs from
    :func:`_flash_fwd_kernel` in what it leaves out (the key mask's select and
    the masked-row guard: module docstring) and in how the row statistics
    meet the score tile: they stay replicated over the 128 lanes and are tiled
    along them, where a ``[block_q, 1]`` column broadcast over the lanes cost
    40 of 113 ms a launch on a v5e (PERF.md section 6, PR 34). The causal
    select stays on every block: its passes run in the products' shadow, and
    a second body for the blocks below the diagonal measured 1.6 ms slower.
    Under a ``window`` a row starts at its diagonal key block and ends at the
    band's first (:func:`_work_steps`), and the band's lower edge is selected
    on beside the causal one."""
    from jax.experimental import pallas as pl

    step = pl.program_id(1)
    q_blk, kv_blk = q_blk_ref[step], kv_blk_ref[step]
    first = 0
    if window:
        first = jnp.minimum(_diagonal_kv_block(q_blk, block_q, block_k), n_kblocks - 1)

    @pl.when(kv_blk == first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kv_pos = kv_blk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos <= q_pos, s, _NEG_INF)
        if window:
            s = jnp.where(kv_pos > q_pos - window, s, _NEG_INF)
    m = m_scr[...]                                      # [block_q, 128], lanes equal
    new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - new_m)
    p = jnp.exp(s - jnp.tile(new_m, (1, block_k // 128)))
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    v_blk = v_ref[0]
    acc_scr[...] = (acc_scr[...] * jnp.tile(alpha, (1, acc_scr.shape[1] // 128))
                    + jax.lax.dot_general(p.astype(v_blk.dtype), v_blk,
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_scr[...] = new_m

    last = n_kblocks - 1
    if window:
        last = jnp.maximum(_band_first_kv_block(q_blk, block_q, block_k, window), 0)
    elif causal:
        last = jnp.minimum(_diagonal_kv_block(q_blk, block_q, block_k), last)

    @pl.when(kv_blk == last)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


# the unmasked variant's two step tables live in SMEM (1 MiB on a v5e; two
# int32 tables of 65,536 steps, 512 KiB, compile for it; 131,328 do not)
_MAX_TABLE_STEPS = 1 << 16


def _work_steps(n_qblocks: int, n_kblocks: int, block_q: int, block_k: int,
                causal: bool, window: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """(query block, key block) of every grid step of the unmasked variant:
    each query block's needed key blocks in order, query blocks in order.
    Under a ``window`` (causal): each query block's key blocks that meet the
    band, from its diagonal block down to the band's first."""
    if window:
        rows = np.arange(n_qblocks)
        hi = np.minimum(_diagonal_kv_block(rows, block_q, block_k), n_kblocks - 1)
        counts = hi - np.maximum(_band_first_kv_block(rows, block_q, block_k, window), 0) + 1
        back = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts, counts)
        return (np.repeat(rows, counts).astype(np.int32),
                (np.repeat(hi, counts) - back).astype(np.int32))
    counts = np.full(n_qblocks, n_kblocks)
    if causal:
        counts = np.minimum(_diagonal_kv_block(np.arange(n_qblocks), block_q, block_k) + 1,
                            counts)
    q_blks = np.repeat(np.arange(n_qblocks), counts)
    kv_blks = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts, counts)
    return q_blks.astype(np.int32), kv_blks.astype(np.int32)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, kv_mask, causal, block_q, block_k, scale, unmasked, window):
    out, _ = _flash_core_fwd_impl(q, k, v, kv_mask, causal, block_q, block_k,
                                  scale, unmasked, window)
    return out


def _flash_core_fwd_impl(q, k, v, kv_mask, causal, block_q, block_k, scale,
                         unmasked, window):
    """q,k: [BH, T, Dp]; v: [BH, T, Dvp] (its own width: the accumulator and
    the output are as wide as the values, not as the keys); kv_mask: [BH, Tk]
    bool, which the ``unmasked`` variant (all True then:
    :func:`_flash_attention` decides) leaves out of the kernel. ``scale`` is
    1/sqrt of the TRUE query width (D may be lane-padded here). Returns
    (out, lse)."""
    from jax.experimental import pallas as pl

    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, Dp = q.shape
    Tk, Dvp = k.shape[1], v.shape[2]
    n_qblocks, n_kblocks = Tq // block_q, Tk // block_k
    static = dict(block_q=block_q, block_k=block_k, n_kblocks=n_kblocks,
                  scale=scale, causal=causal, window=window)
    scratch_shapes = [
        pltpu.VMEM((block_q, 128), jnp.float32),   # running max (lane-bcast)
        pltpu.VMEM((block_q, 128), jnp.float32),   # running sum (lane-bcast)
        pltpu.VMEM((block_q, Dvp), jnp.float32),   # output accumulator
    ]
    if unmasked:
        steps = _work_steps(n_qblocks, n_kblocks, block_q, block_k, causal, window)

        def q_block(b, t, q_blks, kv_blks):
            return (b, q_blks[t], 0)

        def kv_block(b, t, q_blks, kv_blks):
            return (b, kv_blks[t], 0)

        kernel = functools.partial(_flash_fwd_kernel_unmasked, **static)
        operands = (*steps, q, k, v)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BH, len(steps[0])),
            in_specs=[pl.BlockSpec((1, block_q, Dp), q_block),
                      pl.BlockSpec((1, block_k, Dp), kv_block),
                      pl.BlockSpec((1, block_k, Dvp), kv_block)],
            out_specs=[pl.BlockSpec((1, block_q, Dvp), q_block),
                       pl.BlockSpec((1, block_q, 1), q_block)],
            scratch_shapes=scratch_shapes)
    else:
        def kv_block(i, j):
            # above the diagonal the kernel skips the step: name the block
            # already resident, and the pipeline issues no copy
            if window:      # before the band too: name its first block
                j = jnp.maximum(j, _band_first_kv_block(i, block_q, block_k, window))
            return jnp.minimum(j, _diagonal_kv_block(i, block_q, block_k)) if causal else j

        kernel = functools.partial(_flash_fwd_kernel, **static)
        operands = (q, k, v, kv_mask.astype(jnp.int32)[:, None, :])
        grid_spec = pl.GridSpec(
            grid=(BH, n_qblocks, n_kblocks),
            in_specs=[
                pl.BlockSpec((1, block_q, Dp), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, i, j: (b, kv_block(i, j), 0)),
                pl.BlockSpec((1, block_k, Dvp), lambda b, i, j: (b, kv_block(i, j), 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, kv_block(i, j))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dvp), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            scratch_shapes=scratch_shapes)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, Dvp), q.dtype),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        interpret=platform.pallas_interpret(),
    )(*operands)
    return out, lse[:, :, 0]


def _flash_core_fwd(q, k, v, kv_mask, causal, block_q, block_k, scale, unmasked,
                    window):
    out, lse = _flash_core_fwd_impl(q, k, v, kv_mask, causal, block_q, block_k,
                                    scale, unmasked, window)
    # named here, so that the kept output is at once the residual and the
    # value the layer goes on from
    out, lse = checkpoint_name(out, _OUT), checkpoint_name(lse, _LSE)
    return out, (q, k, v, kv_mask, out, lse)


def _flash_core_bwd(causal, block_q, block_k, scale, unmasked, window, res, g):
    """Blockwise XLA backward from the saved log-sum-exp, one pass over the
    (key block, query block) pairs that hold work: a scan over the key blocks,
    inside it a loop over the query blocks that see the key block (all of
    them, or under ``causal`` those at and below the diagonal). A pair forms
    ``s = q k^T``, ``p = exp(s - lse)``, ``dp = g v^T`` and ``ds = p (dp -
    delta)`` once and feeds the three gradients from them: ``dv += p^T g``,
    ``dk += ds^T q``, ``dq[query block] += ds k``: five products. Matmul
    operands stay in the input dtype (bf16 on the training path) with f32
    accumulation; only the softmax/probability statistics are f32. Every sum
    runs from zeros in ascending order of the other side's blocks, as the two
    loop nests this replaced summed (``tests/test_ops.py`` keeps them: equal
    bit for bit in float32).

    ``dk`` and ``dv`` of the key block ride in the inner loop's carry. ``dq``
    is one float32 ``[n_qb, BH, block_q, Dp]`` buffer carried through both
    loops, updated in place: a pair reads its block (a slice fusion of its
    own), adds the product and writes it back in the product's fusion, ``BH x
    block_q x Dp`` float32 through HBM each way (8.4 MB for 32 heads of 64 on
    128 lanes at blocks of 512; 16.8 MB on 256 lanes). On a v5e at the first
    shape a pair takes 81 us of products and 12 us of that read, 2,080 pairs
    and ~207 ms a pass (the two loop nests: 117 us a pair and the mask tile,
    ~257 ms); ``dq.at[qi].add`` compiles to one read-add-write fusion and
    takes the same time.

    ``unmasked`` (decided by :func:`_flash_attention`: the key mask is all
    ones) leaves the mask work out of the pair: no block of the mask is
    sliced or selected on, so no ``[BH, block_q, block_k]`` mask tile is
    built, and no masked-row guard runs (a masked entry is ``-1e30`` against a
    finite log-sum-exp and ``exp`` gives an exact 0; with the guard XLA:TPU
    packs its predicate for all heads into a tile of its own: 22 ms of 225 a
    pass at the first shape).

    Under a ``window`` the inner loop also stops at the last query block that
    sees the key block (a key ``j`` is seen by the queries ``j <= i < j +
    window``), and the pair selects on the band's lower edge beside the
    causal one; every row's log-sum-exp is finite (its own key), so the
    unmasked pair still needs no guard."""
    q, k, v, kv_mask, out, lse = res
    BH, Tq, Dp = q.shape
    Tk, Dvp = k.shape[1], v.shape[2]     # dq, dk as wide as the keys; dv as the values
    gf = g.astype(q.dtype)
    # delta_i = sum_d out_i * g_i  (rowwise), standard flash bwd identity
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    n_qb, n_kb = Tq // block_q, Tk // block_k
    q_pos = jnp.arange(block_q)[None, :, None]
    kv_pos = jnp.arange(block_k)[None, None, :]

    def key_block(dq, ki):
        ki0 = ki * block_k
        kb = jax.lax.dynamic_slice_in_dim(k, ki0, block_k, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, ki0, block_k, axis=1)
        if not unmasked:
            mb = jax.lax.dynamic_slice_in_dim(kv_mask, ki0, block_k, axis=1)

        def pair(qi, carry):
            dq, dk_acc, dv_acc = carry
            qi0 = qi * block_q
            q_blk = jax.lax.dynamic_slice_in_dim(q, qi0, block_q, axis=1)
            lse_blk = jax.lax.dynamic_slice_in_dim(lse, qi0, block_q, axis=1)
            g_blk = jax.lax.dynamic_slice_in_dim(gf, qi0, block_q, axis=1)
            d_blk = jax.lax.dynamic_slice_in_dim(delta, qi0, block_q, axis=1)
            s = jnp.einsum("bqd,bkd->bqk", q_blk, kb,
                           preferred_element_type=jnp.float32) * scale
            if not unmasked:
                s = jnp.where(mb[:, None, :], s, _NEG_INF)
            if causal:
                s = jnp.where(ki0 + kv_pos <= qi0 + q_pos, s, _NEG_INF)
            if window:
                s = jnp.where(ki0 + kv_pos > qi0 + q_pos - window, s, _NEG_INF)
            p = jnp.exp(s - lse_blk[:, :, None])
            if not unmasked:
                # a row with no key left has lse = -1e30 too: exp(0) would count
                # its masked entries (the forward kernel's guard)
                p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
            dv_acc = dv_acc + jnp.einsum("bqk,bqd->bkd", p.astype(g_blk.dtype), g_blk,
                                         preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqd,bkd->bqk", g_blk, vb,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - d_blk[:, :, None])).astype(q.dtype)
            dk_acc = dk_acc + jnp.einsum("bqk,bqd->bkd", ds, q_blk,
                                         preferred_element_type=jnp.float32) * scale
            dq_blk = jax.lax.dynamic_index_in_dim(dq, qi, keepdims=False)
            dq = jax.lax.dynamic_update_index_in_dim(
                dq, dq_blk + jnp.einsum("bqk,bkd->bqd", ds, kb,
                                        preferred_element_type=jnp.float32) * scale, qi, 0)
            return dq, dk_acc, dv_acc

        # causal: query blocks that end before the key block's first row hold no
        # pair; a window: nor those that start after its last key's last query
        stop = n_qb
        if window:
            stop = jnp.minimum((ki0 + block_k + window - 2) // block_q + 1, n_qb)
        dq, dk_blk, dv_blk = jax.lax.fori_loop(
            ki0 // block_q if causal else 0, stop, pair,
            (dq, jnp.zeros((BH, block_k, Dp), jnp.float32),
             jnp.zeros((BH, block_k, Dvp), jnp.float32)))
        return dq, (dk_blk, dv_blk)

    dq_blocks, (dk_blocks, dv_blocks) = jax.lax.scan(
        key_block, jnp.zeros((n_qb, BH, block_q, Dp), jnp.float32), jnp.arange(n_kb))
    dq = jnp.reshape(dq_blocks.transpose(1, 0, 2, 3), (BH, Tq, Dp))
    dk = jnp.reshape(dk_blocks.transpose(1, 0, 2, 3), (BH, Tk, Dp))
    dv = jnp.reshape(dv_blocks.transpose(1, 0, 2, 3), (BH, Tk, Dvp))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, kv_mask=None, causal: bool = False,
                    block_q: int = 128, block_k: int = 128, window: int = 0):
    """Fused blockwise attention. [B, T, H, D] layout, differentiable.

    ``v`` may have a width of its own (``v.shape[-1] != q.shape[-1]``; ``k``
    is as wide as ``q``): the output is as wide as ``v``, the softmax scale is
    1/sqrt of the true QUERY width, and each width is padded to its own whole
    128-lane tiles (192 beside 128 runs 256 lanes of keys and 128 of values,
    accumulator and output; one padded width would do a third more value work
    and output bytes). Equal widths build the kernels they always built.

    Pads T to the block size and each width to the 128-lane TPU tile
    (zero-padding D leaves dot products unchanged; padded kv positions are
    masked; padded q rows are sliced away). A call with no ``kv_mask`` whose
    keys need no padding builds the forward kernel's ``unmasked`` variant (no
    mask operand, no masked-row guard, only the grid steps that hold work: key
    0 makes every row's running maximum finite before a masked entry meets
    it); a mask, a ragged ``Tk`` or key blocks that are not whole 128-lane
    tiles build the ``masked`` one. Same numbers either way (module docstring).

    ``window`` > 0 (``causal`` only): query ``i`` sees keys ``i - window < j
    <= i``, and only the tiles that meet that band are computed, under the
    scope ``attn.window`` (the unmasked variant also needs ``block_k`` a
    multiple of ``block_q``, so that a row's own key is in the first tile it
    meets).
    """
    if window and not causal:
        raise ValueError("flash_attention: a window is a causal band (causal=True)")
    # kernel, pads and the backward's loops alike
    with jax.named_scope("attn.window" if window else "attn.flash"):
        return _flash_attention(q, k, v, kv_mask, causal, block_q, block_k, window)


def _band_pairs(t: int, window: int) -> int:
    """(query, key) pairs of a causal band of ``window`` keys over ``t`` positions."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def _flash_attention(q, k, v, kv_mask, causal: bool, block_q: int, block_k: int,
                     window: int):
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    if k.shape[-1] != D:
        raise ValueError(f"flash_attention: keys of width {k.shape[-1]} against "
                         f"queries of width {D}")
    if causal and Tq != Tk:
        # the kernel aligns q/kv positions at 0 with no offset; a causal mask
        # with Tq != Tk would be silently misaligned (cf. reference_attention's
        # q_offset/kv_offset)
        raise ValueError(f"causal flash_attention requires Tq == Tk, got "
                         f"Tq={Tq} Tk={Tk}")
    block_q = min(block_q, _ceil_to(Tq, 8))
    block_k = min(block_k, _ceil_to(Tk, 8))
    Tq_p, Tk_p = _ceil_to(Tq, block_q), _ceil_to(Tk, block_k)
    # no key is masked or padded, the row statistics tile along whole lanes
    # and the step tables fit in SMEM
    unmasked = (kv_mask is None and Tk_p == Tk and block_k % 128 == 0
                and (Tq_p // block_q) * (Tk_p // block_k) <= _MAX_TABLE_STEPS
                and (not window or block_k % block_q == 0))
    reg = obs.get_registry()
    reg.counter(
        "synapseml_flash_kernel_builds_total",
        "traces of flash_attention, by the forward kernel variant they built",
        ("variant",)).inc(variant="window" if window else "unmasked" if unmasked else "masked")
    if window:      # either variant computes the tiles of the step tables
        tiles = len(_work_steps(Tq_p // block_q, Tk_p // block_k, block_q, block_k, True,
                                window)[0])
        reg.gauge("synapseml_attn_window_pairs_ratio",
                  "(query, key) pairs a windowed flash call's tiles compute over the "
                  "band's, at its latest trace").set(
            tiles * block_q * block_k / _band_pairs(Tq, window))
    if kv_mask is None:
        kv_mask = jnp.ones((B, Tk), bool)
    Dp, Dvp = _ceil_to(D, 128), _ceil_to(Dv, 128)
    scale = 1.0 / np.sqrt(D)  # true query width — padding D must not change it

    def to_bh(x, Tp, Wp):
        x = jnp.pad(x, ((0, 0), (0, Tp - x.shape[1]), (0, 0), (0, Wp - x.shape[3])))
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, Tp, Wp)

    qb = to_bh(q, Tq_p, Dp)
    kb = to_bh(k, Tk_p, Dp)
    vb = to_bh(v, Tk_p, Dvp)
    maskb = jnp.pad(kv_mask, ((0, 0), (0, Tk_p - Tk)))
    maskb = jnp.broadcast_to(maskb[:, None, :], (B, H, Tk_p)).reshape(B * H, Tk_p)

    out = _flash_core(qb, kb, vb, maskb, causal, block_q, block_k, scale, unmasked, window)
    out = out.reshape(B, H, Tq_p, Dvp)[:, :, :Tq, :Dv]
    return jnp.transpose(out, (0, 2, 1, 3))
