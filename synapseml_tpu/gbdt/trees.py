"""On-device histogram tree growth — the TPU replacement for LightGBM's C++ core.

Reference analog: the native hot loop behind ``LGBM_BoosterUpdateOneIter``
(``booster/LightGBMBooster.scala:355``, ``TrainUtils.scala:98``): histogram
construction + allreduce + best-split + partition. The TPU-native redesign:

  * Trees live in fixed-size heap-layout arrays (node ``i`` → children
    ``2i+1``/``2i+2``): static shapes, so every step jits once per depth level
    and is reused across all trees and boosting iterations.
  * Growth is **level-wise**: one batched ``segment_sum`` histogram pass per
    depth computes the histograms of *all* active nodes simultaneously —
    no per-leaf dynamic gathers (which would defeat XLA). LightGBM's
    ``num_leaves`` cap is honored by ranking candidate splits by gain at each
    level and splitting only as many as the remaining leaf budget allows
    (best-first within a level).
  * Rows are sharded over the ``data`` mesh axis; the histogram reduction is
    the cross-device collective (GSPMD inserts the psum from sharding
    annotations) — this *is* the reference's NetworkManager + socket-ring
    allreduce (``NetworkManager.scala:59-125``), expressed as sharding.
  * Missing values (NaN bin = last bin) route right; thresholds never cover
    the NaN bin.

Histogram channels: (grad, hess, count).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["GrowthConfig", "TreeArrays", "grow_tree", "traverse_binned",
           "predict_raw_forest", "level_cum_tables", "split_gain"]


class GrowthConfig(NamedTuple):
    """Static growth hyper-parameters (one jit cache per distinct config)."""

    max_depth: int
    num_leaves: int
    num_bins: int
    lambda_l1: float
    lambda_l2: float
    learning_rate: float
    min_data_in_leaf: int
    min_sum_hessian: float
    min_gain_to_split: float
    # per-feature monotone constraints (+1/-1/0), () = unconstrained
    # (reference params/LightGBMParams.scala monotoneConstraints; the 'basic'
    # method: split-direction gating + child-value midpoint bounds)
    monotone_constraints: tuple = ()
    # histogram backend: 'segment' (segment_sum -> scatter-add), 'onehot'
    # (row-chunked one-hot matmul — MXU-shaped but XLA materializes the
    # one-hot in HBM), or 'pallas' (fused kernel generating one-hot tiles in
    # VMEM — .pallas_hist). Equivalent results; pick by measurement
    hist_impl: str = "segment"
    # categorical features (sorted feature indices; their bins ARE the raw
    # category codes). Split finding is LightGBM's many-vs-many: bins sorted
    # per node by grad/(hess+cat_smooth), prefixes of the sorted order are
    # the candidate left sets — the SAME cumulative-histogram scan as
    # numerical thresholds, just through a per-node permutation (reference
    # params categoricalSlotIndexes, BaseTrainParams.scala)
    categorical_features: tuple = ()
    max_cat_threshold: int = 32
    cat_smooth: float = 10.0


class TreeArrays(NamedTuple):
    """One tree in heap layout; leaf nodes have ``feature == -1``."""

    feature: jax.Array  # (M,) int32, -1 = leaf
    threshold_bin: jax.Array  # (M,) int32, split: bin <= thr goes left
    leaf_value: jax.Array  # (M,) float32
    gain: jax.Array  # (M,) float32, split gain (0 at leaves) — feeds importance
    cover: jax.Array  # (M,) float32, rows reaching the node — feeds TreeSHAP
    # (M, B) uint8 left-membership per bin for categorical splits; (M, 1)
    # zeros when the config has no categorical features. A node is
    # categorical iff its row has any nonzero (valid cat splits always
    # have a nonempty left set)
    cat_mask: jax.Array = None


def max_nodes(max_depth: int) -> int:
    return 2 ** (max_depth + 1) - 1


def _soft_threshold(g: jax.Array, l1: float) -> jax.Array:
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_value(g: jax.Array, h: jax.Array, cfg: GrowthConfig) -> jax.Array:
    return -_soft_threshold(g, cfg.lambda_l1) / (h + cfg.lambda_l2 + 1e-12) * cfg.learning_rate


def _split_score(g: jax.Array, h: jax.Array, cfg: GrowthConfig) -> jax.Array:
    gs = _soft_threshold(g, cfg.lambda_l1)
    return gs * gs / (h + cfg.lambda_l2 + 1e-12)


def _level_histogram(bins: jax.Array, g: jax.Array, h: jax.Array, presence: jax.Array,
                     node_of_row: jax.Array, base: int, width: int, num_bins: int,
                     hist_impl: str = "segment") -> jax.Array:
    """(width, F, B, 3) histograms for the ``width`` nodes of one level.

    Scans over features so peak memory stays O(N) regardless of F. Rows whose
    node is outside [base, base+width) (rows resting in already-final leaves)
    are zero-weighted out. Two backends per feature:

    * 'segment': one segment-sum of (N, 3) into (width*B, 3) — lowers to a
      scatter-add, which TPUs serialize;
    * 'onehot': row-chunked one-hot matmul — the same reduction phrased as
      [C, width*B]^T @ [C, 3] MXU matmuls accumulated over chunks (the
      scaling-book recipe for TPU histograms). One-hot 0/1 values are exact
      in any dtype and the dot accumulates in f32, so results match
      'segment' to float rounding.
    """
    valid = (node_of_row >= base) & (node_of_row < base + width)
    rel = jnp.where(valid, node_of_row - base, 0)
    zero = jnp.zeros_like(g)
    data = jnp.stack([jnp.where(valid, g, zero), jnp.where(valid, h, zero),
                      jnp.where(valid, presence, zero)], axis=-1)  # (N, 3)
    WB = width * num_bins

    if hist_impl == "onehot":
        row_chunk = 4096
        n = data.shape[0]
        pad = (-n) % row_chunk
        if pad:
            data = jnp.pad(data, ((0, pad), (0, 0)))  # zero rows: no effect
            rel = jnp.pad(rel, (0, pad))
        data_r = data.reshape(-1, row_chunk, 3)

        def one_feature(carry, f_bins):
            if pad:
                f_bins = jnp.pad(f_bins, (0, pad))
            seg_r = (rel * num_bins + f_bins.astype(jnp.int32)
                     ).reshape(-1, row_chunk)

            def chunk_step(acc, xs):
                seg_c, data_c = xs
                oh = jax.nn.one_hot(seg_c, WB, dtype=data_c.dtype)  # (C, WB)
                return acc + jax.lax.dot_general(
                    oh, data_c, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), None

            hist, _ = jax.lax.scan(chunk_step,
                                   jnp.zeros((WB, 3), jnp.float32),
                                   (seg_r, data_r))
            return carry, hist.reshape(width, num_bins, 3)
    elif hist_impl == "segment":
        def one_feature(carry, f_bins):
            seg = rel * num_bins + f_bins.astype(jnp.int32)
            hist = jax.ops.segment_sum(data, seg, num_segments=WB)
            return carry, hist.reshape(width, num_bins, 3)
    elif hist_impl == "pallas":
        from .pallas_hist import pallas_segment_histogram

        def one_feature(carry, f_bins):
            seg = rel * num_bins + f_bins.astype(jnp.int32)
            hist = pallas_segment_histogram(seg, data, WB)
            return carry, hist.reshape(width, num_bins, 3)
    else:
        raise ValueError(f"hist_impl must be 'segment', 'onehot' or "
                         f"'pallas', got {hist_impl!r}")

    _, hists = jax.lax.scan(one_feature, 0, jnp.swapaxes(bins, 0, 1))  # (F, W, B, 3)
    return jnp.swapaxes(hists, 0, 1)  # (W, F, B, 3)


def derive_max_depth(max_depth: int, num_leaves: int) -> int:
    """Effective tree depth for a config: the ONE copy of the default-depth
    formula (deep enough for ``num_leaves``, heap-bounded at 12). Serial
    ``train_booster``, the fused sweep, and ``_fused_plan`` grouping all
    call this — a private copy in any of them would let a fused trial train
    a different tree shape than the serial fit of the same config."""
    if max_depth is None or max_depth <= 0:
        max_depth = max(int(np.ceil(np.log2(max(num_leaves, 2)))) + 1, 3)
    return min(max_depth, 12)


def level_cum_tables(hist: jax.Array, num_thresholds: int):
    """Node totals + cumulative left-prefix channels from one level's
    histograms: ``(g_tot, h_tot, c_tot, gl, hl, cl)`` with ``*_tot`` shaped
    (W,) and the left tables (W, F, num_thresholds). Shared by the serial
    per-config step and the fused multi-trial sweep (``gbdt/fused.py``) so
    the two training paths cannot diverge on the prefix-scan math."""
    cum = jnp.cumsum(hist, axis=2)  # (W, F, B, 3)
    total = cum[:, 0, -1, :]  # (W, 3) — feature 0's full sum == node totals
    left = cum[:, :, :num_thresholds, :]  # (W, F, B-1, 3)
    return (total[:, 0], total[:, 1], total[:, 2],
            left[..., 0], left[..., 1], left[..., 2])


def split_gain(g_tot: jax.Array, h_tot: jax.Array, gl: jax.Array,
               hl: jax.Array, cfg) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Candidate split gains (W, F, num_thresholds) plus the right-side
    grad/hess tables. ``cfg`` only needs ``lambda_l1``/``lambda_l2`` — python
    floats on the serial path, traced per-trial scalars on the fused one."""
    gr = g_tot[:, None, None] - gl
    hr = h_tot[:, None, None] - hl
    gain = (_split_score(gl, hl, cfg) + _split_score(gr, hr, cfg)
            - _split_score(g_tot, h_tot, cfg)[:, None, None])
    return gr, hr, gain


def split_ok_mask(cl, cr, hl, hr, cfg):
    """Data-count / hessian-mass split validity (W, F, num_thresholds).
    ``cfg`` needs ``min_data_in_leaf``/``min_sum_hessian`` — python floats
    on the serial path, traced per-trial scalars on the fused one. Shared
    so the two paths cannot diverge on the eligibility rule."""
    return ((cl >= cfg.min_data_in_leaf) & (cr >= cfg.min_data_in_leaf)
            & (hl >= cfg.min_sum_hessian) & (hr >= cfg.min_sum_hessian))


def select_level_splits(gain, c_tot, leaf_count, cfg, width: int,
                        num_thresholds: int):
    """Best split per node + the level's leaf-budget decision: argmax over
    (feature, threshold) — jnp.argmax's first-max tie-break IS part of the
    contract — min_gain gate, and top-(remaining-budget) ranking by gain.
    ``cfg`` needs ``min_gain_to_split``/``num_leaves``. One copy shared by
    the serial level step and the fused sweep; returns
    ``(best_idx, best_gain, best_feat, best_thr, active, do_split)``."""
    flat = gain.reshape(width, -1)
    best_idx = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
    best_feat = (best_idx // num_thresholds).astype(jnp.int32)
    best_thr = (best_idx % num_thresholds).astype(jnp.int32)
    # a node is "active" at this level iff it actually holds rows
    active = c_tot > 0
    can_split = active & (best_gain > cfg.min_gain_to_split)
    # leaf budget: each split nets +1 leaf; split the top-(budget) gains
    budget = jnp.maximum(cfg.num_leaves - leaf_count, 0)
    order = jnp.argsort(jnp.where(can_split, -best_gain, jnp.inf))
    rank = jnp.zeros(width, jnp.int32).at[order].set(
        jnp.arange(width, dtype=jnp.int32))
    do_split = can_split & (rank < budget)
    return best_idx, best_gain, best_feat, best_thr, active, do_split


def level_row_partition(bins, node_of_row, do_split, best_feat, best_thr,
                        base: int, width: int):
    """Row→child routing ingredients for one level: which rows sit in a
    splitting node, their winning feature's bin, and the numeric
    left/right decision. Returns ``(rel, row_split, f_of_row, row_bin,
    go_left)`` — callers may override ``go_left`` (categorical membership)
    before applying :func:`route_rows`."""
    here = (node_of_row >= base) & (node_of_row < base + width)
    rel = jnp.where(here, node_of_row - base, 0)
    row_split = do_split[rel] & here
    f_of_row = best_feat[rel]
    row_bin = jnp.take_along_axis(
        bins, f_of_row[:, None].astype(jnp.int32), axis=1)[:, 0]
    go_left = row_bin.astype(jnp.int32) <= best_thr[rel]
    return rel, row_split, f_of_row, row_bin, go_left


def route_rows(node_of_row, row_split, go_left):
    """Move each splitting row to its heap child (left = 2i+1)."""
    child = 2 * node_of_row + jnp.where(go_left, 1, 2)
    return jnp.where(row_split, child, node_of_row)


def _make_level_step(base: int, width: int, cfg: GrowthConfig):
    """One jitted level step: histogram → best splits → budget → update tree +
    row partition. Reused across trees/iterations (same shapes)."""

    B = cfg.num_bins
    num_thresholds = B - 1  # thresholds 0..B-2; the NaN bin is never a left-inclusive cut

    mono = (np.asarray(cfg.monotone_constraints, np.int32)
            if any(cfg.monotone_constraints) else None)

    @jax.jit
    def step(bins, grad, hess, presence, node_of_row, feature, threshold_bin,
             leaf_value, node_gain, node_cover, feat_mask, leaf_count,
             node_lo, node_hi, cat_mask_tree):
        hist = _level_histogram(bins, grad, hess, presence, node_of_row, base,
                                width, B, hist_impl=cfg.hist_impl)
        g_tot, h_tot, c_tot, gl, hl, cl = level_cum_tables(hist,
                                                           num_thresholds)

        cat_order = None
        if cfg.categorical_features:
            F = bins.shape[1]
            cat_idx = np.asarray(cfg.categorical_features, np.int32)
            is_cat_f = np.zeros(F, bool)
            is_cat_f[cat_idx] = True
            # candidate left sets = prefixes of bins sorted by g/(h+smooth);
            # zero-count bins and the NaN bin (last) are never members, so
            # unseen/missing categories route right at predict time. Only
            # the CATEGORICAL columns pay the argsort/cumsum (static gather
            # + scatter-back keeps numerical columns untouched).
            hist_c = hist[:, cat_idx]  # (W, Fc, B, 3)
            gb, hb, cb = hist_c[..., 0], hist_c[..., 1], hist_c[..., 2]
            eligible = (cb > 0) & (jnp.arange(B) != B - 1)[None, None, :]
            ratio = jnp.where(eligible, gb / (hb + cfg.cat_smooth), jnp.inf)
            cat_order = jnp.argsort(ratio, axis=2)  # (W, Fc, B)
            sg = jnp.take_along_axis(jnp.where(eligible, gb, 0.0), cat_order, 2)
            sh = jnp.take_along_axis(jnp.where(eligible, hb, 0.0), cat_order, 2)
            sc = jnp.take_along_axis(jnp.where(eligible, cb, 0.0), cat_order, 2)
            s_ok = jnp.take_along_axis(eligible, cat_order, 2)
            gl = gl.at[:, cat_idx].set(jnp.cumsum(sg, axis=2)[:, :, :num_thresholds])
            hl = hl.at[:, cat_idx].set(jnp.cumsum(sh, axis=2)[:, :, :num_thresholds])
            cl = cl.at[:, cat_idx].set(jnp.cumsum(sc, axis=2)[:, :, :num_thresholds])
            # prefix k (index k-1) valid iff its last bin is eligible and the
            # left set stays within max_cat_threshold categories
            valid_k = (s_ok[:, :, :num_thresholds]
                       & (jnp.arange(num_thresholds) < cfg.max_cat_threshold
                          )[None, None, :])
            # position of each cat feature within cat_idx (for the winning
            # node's order lookup below)
            cat_pos = np.zeros(F, np.int32)
            cat_pos[cat_idx] = np.arange(len(cat_idx), dtype=np.int32)

        gr, hr, gain = split_gain(g_tot, h_tot, gl, hl, cfg)
        cr = c_tot[:, None, None] - cl
        ok = split_ok_mask(cl, cr, hl, hr, cfg) & feat_mask[None, :, None]
        if cfg.categorical_features:
            ok = ok.at[:, cat_idx].set(ok[:, cat_idx] & valid_k)
        if mono is not None:
            # monotone gating: a split on a constrained feature is only valid
            # if the would-be child values respect the direction
            vl = _leaf_value(gl, hl, cfg)
            vr = _leaf_value(gr, hr, cfg)
            c = jnp.asarray(mono)[None, :, None]
            ok &= jnp.where(c > 0, vl <= vr, jnp.where(c < 0, vl >= vr, True))
        gain = jnp.where(ok, gain, -jnp.inf)

        (best_idx, best_gain, best_feat, best_thr, active,
         do_split) = select_level_splits(gain, c_tot, leaf_count, cfg,
                                         width, num_thresholds)

        node_ids = base + jnp.arange(width, dtype=jnp.int32)
        feature = feature.at[node_ids].set(jnp.where(do_split, best_feat, -1))
        threshold_bin = threshold_bin.at[node_ids].set(jnp.where(do_split, best_thr, 0))

        member = None
        if cfg.categorical_features:
            # materialize the winning left set: bins whose rank in the
            # node's sorted order falls inside the chosen prefix
            best_cat_pos = jnp.asarray(cat_pos)[best_feat]
            best_order = jnp.take_along_axis(
                cat_order, best_cat_pos[:, None, None], axis=1)[:, 0]  # (W, B)
            inv_rank = jnp.argsort(best_order, axis=-1)  # inverse permutation
            is_cat_best = jnp.asarray(is_cat_f)[best_feat]
            member = ((inv_rank <= best_thr[:, None])
                      & (is_cat_best & do_split)[:, None])  # (W, B)
            cat_mask_tree = cat_mask_tree.at[node_ids].set(
                member.astype(jnp.uint8))
        lo = node_lo[node_ids]
        hi = node_hi[node_ids]
        # active nodes that do not split become final leaves now (clamped to
        # the monotone bounds inherited from ancestors)
        value = jnp.clip(_leaf_value(g_tot, h_tot, cfg), lo, hi)
        leaf_value = leaf_value.at[node_ids].set(jnp.where(active & ~do_split, value, 0.0))
        node_gain = node_gain.at[node_ids].set(jnp.where(do_split, best_gain, 0.0))
        node_cover = node_cover.at[node_ids].set(c_tot)
        leaf_count = leaf_count + jnp.sum(do_split.astype(jnp.int32))

        # propagate monotone bounds to children: on a +1 split the left
        # subtree is capped at the midpoint and the right floored (basic
        # method); unconstrained splits inherit the parent bounds
        left_ids = 2 * node_ids + 1
        right_ids = 2 * node_ids + 2
        if mono is not None:
            bvl = jnp.take_along_axis(
                _leaf_value(gl, hl, cfg).reshape(width, -1), best_idx[:, None], 1)[:, 0]
            bvr = jnp.take_along_axis(
                _leaf_value(gr, hr, cfg).reshape(width, -1), best_idx[:, None], 1)[:, 0]
            mid = jnp.clip((bvl + bvr) * 0.5, lo, hi)
            cf = jnp.asarray(mono)[best_feat]
            l_hi = jnp.where(do_split & (cf > 0), jnp.minimum(hi, mid), hi)
            r_lo = jnp.where(do_split & (cf > 0), jnp.maximum(lo, mid), lo)
            l_lo = jnp.where(do_split & (cf < 0), jnp.maximum(lo, mid), lo)
            r_hi = jnp.where(do_split & (cf < 0), jnp.minimum(hi, mid), hi)
        else:
            l_lo, l_hi, r_lo, r_hi = lo, hi, lo, hi
        node_lo = node_lo.at[left_ids].set(l_lo)
        node_hi = node_hi.at[left_ids].set(l_hi)
        node_lo = node_lo.at[right_ids].set(r_lo)
        node_hi = node_hi.at[right_ids].set(r_hi)

        # partition rows of split nodes to children
        rel, row_split, f_of_row, row_bin, go_left = level_row_partition(
            bins, node_of_row, do_split, best_feat, best_thr, base, width)
        if cfg.categorical_features:
            in_set = jnp.take_along_axis(
                member[rel], row_bin[:, None].astype(jnp.int32), axis=1)[:, 0]
            go_left = jnp.where(jnp.asarray(is_cat_f)[f_of_row], in_set,
                                go_left)
        node_of_row = route_rows(node_of_row, row_split, go_left)
        return (node_of_row, feature, threshold_bin, leaf_value, node_gain,
                node_cover, leaf_count, node_lo, node_hi, cat_mask_tree)

    return step


def _make_final_level(base: int, width: int, cfg: GrowthConfig):
    """At max depth every active node becomes a leaf (no histogram needed —
    just per-node g/h totals)."""

    @jax.jit
    def step(grad, hess, presence, node_of_row, leaf_value, node_cover,
             node_lo, node_hi):
        valid = (node_of_row >= base) & (node_of_row < base + width)
        rel = jnp.where(valid, node_of_row - base, 0)
        zero = jnp.zeros_like(grad)
        data = jnp.stack([jnp.where(valid, grad, zero), jnp.where(valid, hess, zero),
                          jnp.where(valid, presence, zero)], axis=-1)
        tot = jax.ops.segment_sum(data, rel, num_segments=width)  # (W, 3)
        active = tot[:, 2] > 0
        node_ids = base + jnp.arange(width, dtype=jnp.int32)
        value = jnp.clip(_leaf_value(tot[:, 0], tot[:, 1], cfg),
                         node_lo[node_ids], node_hi[node_ids])
        return (leaf_value.at[node_ids].set(jnp.where(active, value, 0.0)),
                node_cover.at[node_ids].set(tot[:, 2]))

    return step


@functools.lru_cache(maxsize=None)
def _level_steps(cfg: GrowthConfig):
    steps = [_make_level_step(2**d - 1, 2**d, cfg) for d in range(cfg.max_depth)]
    final = _make_final_level(2**cfg.max_depth - 1, 2**cfg.max_depth, cfg)
    return steps, final


def grow_tree(bins: jax.Array, grad: jax.Array, hess: jax.Array, presence: jax.Array,
              cfg: GrowthConfig, feat_mask: jax.Array) -> TreeArrays:
    """Grow one tree. ``bins`` (N, F) int; ``grad``/``hess`` (N,) float32
    (sample weights / bagging already folded in); ``presence`` (N,) float32
    0/1 marks real vs padded/bagged-out rows (drives the count channel);
    ``feat_mask`` (F,) bool."""
    m = max_nodes(cfg.max_depth)
    feature = jnp.full(m, -1, jnp.int32)
    threshold_bin = jnp.zeros(m, jnp.int32)
    leaf_value = jnp.zeros(m, jnp.float32)
    node_gain = jnp.zeros(m, jnp.float32)
    node_cover = jnp.zeros(m, jnp.float32)
    node_lo = jnp.full(m, -jnp.inf, jnp.float32)
    node_hi = jnp.full(m, jnp.inf, jnp.float32)
    node_of_row = jnp.zeros(bins.shape[0], jnp.int32)
    leaf_count = jnp.asarray(1, jnp.int32)
    cat_width = cfg.num_bins if cfg.categorical_features else 1
    cat_mask = jnp.zeros((m, cat_width), jnp.uint8)

    steps, final = _level_steps(cfg)
    for step in steps:
        (node_of_row, feature, threshold_bin, leaf_value, node_gain, node_cover,
         leaf_count, node_lo, node_hi, cat_mask) = step(
            bins, grad, hess, presence, node_of_row, feature, threshold_bin,
            leaf_value, node_gain, node_cover, feat_mask, leaf_count,
            node_lo, node_hi, cat_mask)
    leaf_value, node_cover = final(grad, hess, presence, node_of_row,
                                   leaf_value, node_cover, node_lo, node_hi)
    return TreeArrays(feature, threshold_bin, leaf_value, node_gain, node_cover,
                      cat_mask)


@functools.partial(jax.jit, static_argnums=(2,))
def traverse_binned(bins: jax.Array, tree: TreeArrays, max_depth: int) -> jax.Array:
    """Leaf values for binned rows (used to update train scores incrementally).
    A node routes categorically iff its cat_mask row is nonempty (valid
    categorical splits always have a nonempty left set)."""
    has_cat = tree.cat_mask is not None and tree.cat_mask.shape[1] > 1

    def body(_, node):
        f = tree.feature[node]
        b = jnp.take_along_axis(bins, jnp.maximum(f, 0)[:, None].astype(jnp.int32), axis=1)[:, 0]
        go_left = b.astype(jnp.int32) <= tree.threshold_bin[node]
        if has_cat:
            mask_row = tree.cat_mask[node]  # (N, B)
            is_cat = mask_row.sum(axis=1) > 0
            in_set = jnp.take_along_axis(
                mask_row, b[:, None].astype(jnp.int32), axis=1)[:, 0] > 0
            go_left = jnp.where(is_cat, in_set, go_left)
        child = 2 * node + jnp.where(go_left, 1, 2)
        return jnp.where(f < 0, node, child)

    node = jax.lax.fori_loop(0, max_depth, body,
                             jnp.zeros(bins.shape[0], jnp.int32))
    return tree.leaf_value[node]


def cat_route_left(fv: jax.Array, go_left: jax.Array,
                   mask_node: jax.Array | None) -> jax.Array:
    """Overlay categorical routing on a numerical go-left decision: nodes
    whose mask row is nonempty route by left-set membership of the raw
    category code; NaN / out-of-range / non-members route right. THE single
    routing rule — shared by raw prediction, leaf indexing, and the
    imported-model walker so they cannot diverge."""
    if mask_node is None:
        return go_left
    B = mask_node.shape[-1]
    is_cat = mask_node.sum(axis=-1) > 0
    idx = jnp.clip(fv.astype(jnp.int32), 0, B - 1)
    in_set = (jnp.take_along_axis(mask_node, idx[:, None], axis=1)[:, 0] > 0) \
        & (fv >= 0) & (fv < B)
    return jnp.where(is_cat, in_set, go_left)


def predict_raw_forest(x: jax.Array, feature: jax.Array, threshold_value: jax.Array,
                       leaf_value: jax.Array, max_depth: int,
                       cat_masks: jax.Array | None = None) -> jax.Array:
    """Raw-feature forest prediction (standalone model, no BinMapper needed).

    ``feature``/``threshold_value``/``leaf_value``: (T, M) stacked trees;
    ``cat_masks``: optional (T, M, B) uint8 — for categorical nodes the raw
    value IS the category code, membership routes left. Returns per-tree
    leaf sums (N,). NaN/out-of-range features route right (comparisons with
    NaN are False; non-members route right), matching training's
    NaN-bin-goes-right rule.
    """

    def _go_left(fv, thr_node, mask_node):
        return cat_route_left(fv, fv <= thr_node, mask_node)

    def one_tree(carry, tree):
        feat, thr, val, cm = tree

        def body(_, node):
            f = feat[node]
            fv = jnp.take_along_axis(x, jnp.maximum(f, 0)[:, None].astype(jnp.int32), axis=1)[:, 0]
            go_left = _go_left(fv, thr[node], None if cm is None else cm[node])
            child = 2 * node + jnp.where(go_left, 1, 2)
            return jnp.where(f < 0, node, child)

        node = jax.lax.fori_loop(0, max_depth, body, jnp.zeros(x.shape[0], jnp.int32))
        return carry + val[node], None

    out, _ = jax.lax.scan(one_tree, jnp.zeros(x.shape[0], jnp.float32),
                          (feature, threshold_value, leaf_value, cat_masks))
    return out


def leaf_index_forest(x: jax.Array, feature: jax.Array, threshold_value: jax.Array,
                      max_depth: int,
                      cat_masks: jax.Array | None = None) -> jax.Array:
    """Per-tree leaf index for each row, shape (N, T) — the reference's
    ``predictLeaf`` output (``LightGBMBooster.scala:394`` area)."""

    def one_tree(carry, tree):
        feat, thr, cm = tree

        def body(_, node):
            f = feat[node]
            fv = jnp.take_along_axis(x, jnp.maximum(f, 0)[:, None].astype(jnp.int32), axis=1)[:, 0]
            go_left = cat_route_left(fv, fv <= thr[node],
                                     None if cm is None else cm[node])
            child = 2 * node + jnp.where(go_left, 1, 2)
            return jnp.where(f < 0, node, child)

        node = jax.lax.fori_loop(0, max_depth, body, jnp.zeros(x.shape[0], jnp.int32))
        return carry, node

    _, nodes = jax.lax.scan(one_tree, 0, (feature, threshold_value, cat_masks))
    return jnp.swapaxes(nodes, 0, 1)
