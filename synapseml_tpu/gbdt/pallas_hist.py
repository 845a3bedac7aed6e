"""Pallas TPU histogram kernel — the GBDT hot loop's third backend.

Reference analog: the CUDA/C++ histogram construction inside
``LGBM_BoosterUpdateOneIter`` (``booster/LightGBMBooster.scala:355``). The
XLA backends in :mod:`.trees` both have a structural weakness on TPU:

* ``segment`` lowers to a scatter-add, which the TPU serializes row by row;
* ``onehot`` phrases the reduction as one-hot matmuls, but XLA materializes
  the ``[chunk, width*bins]`` one-hot operand in HBM every chunk — the
  histogram becomes HBM-bandwidth-bound on a matrix of zeros.

This kernel keeps the one-hot trick but generates each tile ON THE FLY in
VMEM (an iota-compare against the segment ids) and feeds the MXU directly:
HBM traffic is one stream over (seg, grad, hess, count) per feature, nothing
else. Grid = (bin-tiles, row-chunks) with chunks innermost, so each output
tile stays VMEM-resident while every chunk accumulates into it.

Layout (what the Mosaic lowering accepts — every block's last two dims are
multiples of (8, 128)): rows are laid ``_LANES`` to a lane-row; one grid
step takes ``_SUB_ROWS`` lane-rows of segment ids as an ``(8, _LANES)``
block and the matching ``(8, 8, _LANES)`` block of data, where each
lane-row's data is an aligned ``(8, _LANES)`` tile holding grad, hess, count
on sublanes 0..2 and zeros below. Per lane-row the product is
``data[8, L] . onehot[bin_tile, L]^T`` (both operands contract their lane
axis, the q.k^T form), so the histogram comes out TRANSPOSED as a lane-dense
``(8, bin_tile)`` tile and the wrapper slices rows 0..2 back to ``(WB, 3)``.

Interpret mode makes the same kernel run (slowly) on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import platform

__all__ = ["pallas_segment_histogram"]

_LANES = 512          # rows per lane-row (last block dim, multiple of 128)
_SUB_ROWS = 8         # lane-rows per grid step (the sublane tile)
_BIN_TILE = 512       # histogram slots per output tile (lanes of the output)


def _hist_kernel(seg_ref, data_ref, out_ref, *, bin_tile: int):
    """One (bin-tile j, row-chunk c) program: out[:, j] += data . onehot^T.

    seg block [_SUB_ROWS, _LANES] int32; data block [_SUB_ROWS, 8, _LANES]
    f32; out block [8, bin_tile] f32 (revisited across the chunk dimension —
    accumulate, init at the first chunk).
    """
    from jax.experimental import pallas as pl

    j = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # one-hot tile generated in VMEM: bins_col[b, r] = j*bin_tile + b
    bins_col = j * bin_tile + jax.lax.broadcasted_iota(
        jnp.int32, (bin_tile, _LANES), 0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(_SUB_ROWS):
        oh = (seg_ref[i:i + 1, :] == bins_col).astype(jnp.float32)
        # HIGHEST: grad/hess are f32 and a one-pass bf16 product would
        # round them; the one-hot side is exact either way
        acc += jax.lax.dot_general(
            data_ref[i], oh, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    out_ref[...] += acc


@functools.partial(jax.jit, static_argnums=(2,))
def pallas_segment_histogram(seg: jax.Array, data: jax.Array,
                             num_segments: int) -> jax.Array:
    """``segment_sum(data, seg, num_segments)`` as a Pallas TPU kernel.

    seg: (N,) int32 in [0, num_segments) — out-of-range ids contribute
    nowhere (the padding convention). data: (N, 3) f32 (grad, hess, count).
    Returns (num_segments, 3) f32.
    """
    from jax.experimental import pallas as pl

    N = seg.shape[0]
    chunk = _SUB_ROWS * _LANES
    n_chunks = max(-(-N // chunk), 1)
    n_pad = n_chunks * chunk - N
    bin_tile = min(_BIN_TILE, -(-num_segments // 128) * 128)
    n_tiles = -(-num_segments // bin_tile)
    wb_pad = n_tiles * bin_tile

    # padded rows get seg = wb_pad: matches no bin tile, contributes nothing
    seg_p = jnp.pad(seg.astype(jnp.int32), (0, n_pad),
                    constant_values=wb_pad).reshape(-1, _LANES)
    # (N, 3) -> (lane-rows, 8, _LANES): channels on sublanes 0..2
    data_p = jnp.pad(data.astype(jnp.float32).T, ((0, 5), (0, n_pad)))
    data_p = data_p.reshape(8, -1, _LANES).transpose(1, 0, 2)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, bin_tile=bin_tile),
        grid=(n_tiles, n_chunks),
        in_specs=[
            pl.BlockSpec((_SUB_ROWS, _LANES), lambda j, c: (c, 0)),
            pl.BlockSpec((_SUB_ROWS, 8, _LANES), lambda j, c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((8, bin_tile), lambda j, c: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, wb_pad), jnp.float32),
        interpret=platform.pallas_interpret(),
    )(seg_p, data_p)
    return out[:3, :num_segments].T
