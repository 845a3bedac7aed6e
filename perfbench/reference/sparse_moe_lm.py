"""The plain reference of a decoder with learned sparse attention and routed
experts (the language model of Keye-VL-2.0-30B-A3B, cut to one chip's share):
float32 `jax.numpy` at the highest matmul precision, its loss, its gradient
and AdamW with global-norm clipping.

It imports nothing of `synapseml_tpu` and takes nothing the program made.
Weights come from `init_params(seed)`, in this file's own naming; the program
is handed the same weights through `perfbench/programs/sparse_moe_lm.py`.

Every layer, for a row x in R^{T x H} (published description: config.json of
Kwai-Keye/Keye-VL-2.0-30B-A3B; the indexer as DeepSeek sparse attention's;
each size the source lacks is under `assumed` in the configuration file):
  h = RMSNorm(x); q, k, v = h Wq, h Wk, h Wv; q', k' = RoPE(RMSNorm_head(q)),
      RoPE(RMSNorm_head(k)), half-split pairing, positions 0..T-1;
  indexer, on stop_gradient(h): qI = RoPE(h WqI) [T, HI, DI], kI = RoPE(h WkI)
      [T, DI], w = h Ww [T, HI]; I[t, s] = (HI DI)^-1/2 sum_j w[t, j]
      relu(qI[t, j] . kI[s]) for s <= t;
  S_t = the min(t + 1, topk) positions s <= t with the largest I[t, s]
      (equal scores lowest index first, `lax.top_k`'s order);
  a[t, h, .] = softmax over S_t of q'[t, h] . k'[s, g(h)] / sqrt(D);
  y = x + concat_h(sum_{s in S_t} a[t, h, s] v[s, g(h)]) Wo;
  L_I = mean_t KL(stop_gradient(mean_h a[t, h, .]) || softmax over S_t of I[t, .]);
  u = RMSNorm(y); r = softmax(u Wr) over ALL the model's experts; E_t = its
      `top_k` largest; g[t, e] = r_e / sum_{E_t} r;
  z = y + sum over e in E_t held here of g[t, e] (SiLU(u Wg_e) * (u Wu_e)) Wd_e.
After the last layer RMSNorm and the head. Loss = mean cross-entropy over the
positions whose label is not negative + aux_weight x mean over layers of
(experts x sum_e f_e P_e, f_e the share of the batch's (token, choice) pairs
that chose e, P_e the batch's mean r_e) + indexer_weight x sum over layers of
L_I. Departures from the released DSA code: no LayerNorm on the indexer's key.

A batch goes in blocks of rows. f_e is a number of the whole batch, so one
forward pass over all blocks counts it before the gradient pass.

`precision`: "float32" (the reference) or "fp8" (the control: every matrix
product's operands rounded to float8_e4m3fn, one scale a tensor).
Planted faults, for the comparison's own tests and readings: `window_fault`
takes the last `topk` positions in the indexer's place; `raw_gates` leaves the
routing weights unnormalised (g = r_e).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.lib.norms import moment_and_change
from perfbench.reference.encoder import _einsum, fold_seed

F32 = jnp.float32
QUERY_BLOCK = 256        # queries whose scores are alive at a time, where they divide T
LOGIT_BLOCK = 1024       # positions whose logits are alive at a time, where they divide T


def _in_blocks(fn, size: int, *arrays):
    """`fn(first position, *blocks)` over blocks of `size` positions (axis 1) of
    `arrays`, one after the other, rematerialised; the results stacked. All
    positions at once where `size` does not divide them."""
    t = arrays[0].shape[1]
    size = size if t % size == 0 else t
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((a.shape[0], t // size, size) + a.shape[2:]), 1, 0)
    return jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs),
                       (jnp.arange(0, t, size), *map(split, arrays)))


def sizes(config: dict) -> dict:
    """The numbers the reference needs, from a configuration file's own keys."""
    sa = config["sa_config"]
    share = int(config["expert_share"].split(" of ")[0])     # "0 of 8"
    return {"hidden": int(config["hidden_size"]), "layers": int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]), "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]), "vocab": int(config["vocab_size"]),
            "idx_heads": int(sa["indexer_num_heads"]), "idx_dim": int(sa["indexer_head_dim"]),
            "topk": int(sa["topk"]),
            "held": int(config["num_experts"]),
            "experts": int(config["published_num_experts"]),
            "first_expert": share * int(config["num_experts"]),
            "per_token": int(config["num_experts_per_tok"]),
            "expert_width": int(config["moe_intermediate_size"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "aux_weight": float(config["moe_aux_weight"]),
            "indexer_weight": float(config["indexer_loss_weight"])}


def param_shapes(s: dict) -> dict:
    h, n, d = s["hidden"], s["layers"], s["head_dim"]
    e, m = s["held"], s["expert_width"]
    layer = {"ln1": (h,), "wq": (h, s["heads"] * d), "wk": (h, s["kv_heads"] * d),
             "wv": (h, s["kv_heads"] * d), "wo": (s["heads"] * d, h),
             "q_norm": (d,), "k_norm": (d,),
             "iq": (h, s["idx_heads"] * s["idx_dim"]), "ik": (h, s["idx_dim"]),
             "iw": (h, s["idx_heads"]),
             "ln2": (h,), "router": (h, s["experts"]),
             "wg": (e, h, m), "wu": (e, h, m), "wd": (e, m, h)}
    return {"layers": {k: (n,) + v for k, v in layer.items()},
            "embed": (s["vocab"], h), "final_norm": (h,), "head": (h, s["vocab"])}


GAINS = ("ln1", "ln2", "q_norm", "k_norm")


def leaf_sizes(s: dict) -> dict:
    """How many numbers each leaf holds, under `lib/norms.py`'s flat names."""
    out = {}
    for name, shape in param_shapes(s).items():
        if name == "layers":
            for lname, stacked in shape.items():
                for i in range(stacked[0]):
                    out[f"layer{i}.{lname}"] = math.prod(stacked[1:])
        else:
            out[name] = math.prod(shape)
    return out


def init_params(s: dict, seed: int) -> dict:
    """`seed` is below 2**31 (`fold_seed`). Every leaf random from it: N(0, 0.02)
    for matrices and the embedding, 1 + N(0, 0.02) for RMSNorm gains."""
    shapes = param_shapes(s)
    flat, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    p = jax.tree.unflatten(treedef, [0.02 * jax.random.normal(k, shp, F32)
                                     for k, shp in zip(keys, flat)])
    for name in GAINS:
        p["layers"][name] = 1.0 + p["layers"][name]
    p["final_norm"] = 1.0 + p["final_norm"]
    return p


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _softmax_over(x, chosen, log=False):
    """Softmax (or its log) over the last axis, `chosen`'s positions alone. The
    row maximum passes an optimization barrier, which changes no number: without
    it XLA:TPU finds the maximum again for every element in the rematerialised
    backward pass (a reduce-window as wide as the row; PERF.md, PR 29)."""
    x = jnp.where(chosen, x, -jnp.inf)
    shifted = x - jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True)))
    if log:
        return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    e = jnp.exp(shifted)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _rope(x, theta):
    """x [B, T, heads, D]; half-split pairing, positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def key_sets(s: dict, index, first_query: int, window_fault: bool = False):
    """[B, Q, T] mask of each query's key set from index scores [B, Q, T] of the
    queries `first_query`.. against all T keys."""
    q, t = index.shape[1], index.shape[2]
    pos_q = first_query + jnp.arange(q)
    causal = jnp.arange(t)[None, :] <= pos_q[:, None]
    if window_fault:
        return jnp.broadcast_to(
            causal & (jnp.arange(t)[None, :] > pos_q[:, None] - s["topk"]), index.shape)
    # rank of every key among its query's candidates, largest score first and
    # equal scores lowest index first (`lax.top_k`'s order), by two stable
    # sorts; a scatter of `lax.top_k`'s indices says the same and takes the
    # chip minutes
    order = jnp.argsort(-jnp.where(causal[None], index, -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < s["topk"]) & causal[None]


def attention(s: dict, precision: str, lp: dict, h, *, window_fault: bool = False,
              want_sets: bool = False):
    """(attention's output before the residual, L_I summed over the block's
    queries) for normed input h [B, T, H]; with `want_sets` the key sets too."""
    ein = functools.partial(_einsum, precision)
    b, t, _ = h.shape
    heads, kv, d = s["heads"], s["kv_heads"], s["head_dim"]
    hi, di = s["idx_heads"], s["idx_dim"]
    q = ein("bth,hk->btk", h, lp["wq"]).reshape(b, t, heads, d)
    k = ein("bth,hk->btk", h, lp["wk"]).reshape(b, t, kv, d)
    v = ein("bth,hk->btk", h, lp["wv"]).reshape(b, t, kv, d)
    q = _rope(_rms(q, lp["q_norm"], s["eps"]), s["theta"])
    k = _rope(_rms(k, lp["k_norm"], s["eps"]), s["theta"])
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    hs = jax.lax.stop_gradient(h)
    qi = _rope(ein("bth,hk->btk", hs, lp["iq"]).reshape(b, t, hi, di), s["theta"])
    ki = _rope(ein("bth,hk->btk", hs, lp["ik"])[:, :, None, :], s["theta"])[:, :, 0, :]
    wi = ein("bth,hk->btk", hs, lp["iw"])

    def block(first, qb, qib, wib):
        index = jnp.einsum("btj,bjts->bts", wib,
                           jax.nn.relu(ein("btjd,bsd->bjts", qib, ki))) / math.sqrt(hi * di)
        chosen = key_sets(s, jax.lax.stop_gradient(index), first, window_fault)
        scores = ein("bqnd,bknd->bnqk", qb, k) / math.sqrt(d)
        probs = _softmax_over(scores, chosen[:, None])
        out = ein("bnqk,bknd->bqnd", probs, v)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        log_index = _softmax_over(index, chosen, log=True)
        live = chosen & (target > 0)
        kl = jnp.sum(jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                               - jnp.where(live, log_index, 0.0)), 0.0))
        return (out, kl, chosen) if want_sets else (out, kl)

    got = _in_blocks(block, QUERY_BLOCK, q, qi, wi)
    out = jnp.moveaxis(got[0], 0, 1).reshape(b, t, heads * d)
    out = ein("btk,kh->bth", out, lp["wo"])
    if want_sets:
        return out, jnp.sum(got[1]), jnp.moveaxis(got[2], 0, 1).reshape(b, t, t)
    return out, jnp.sum(got[1])


def route(s: dict, precision: str, lp: dict, u, raw_gates: bool = False):
    """(r [S, experts], chosen experts [S, k], their gates [S, k]) for tokens u [S, H]."""
    r = jax.nn.softmax(_einsum(precision, "sh,he->se", u, lp["router"]), axis=-1)
    gates, chosen = jax.lax.top_k(r, s["per_token"])
    if s["norm_topk"] and not raw_gates:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return r, chosen, gates


def experts(s: dict, precision: str, lp: dict, u, chosen, gates, first_expert=None):
    """The held experts' part of the layer's result for tokens u [S, H]: every
    held expert on every token, weighed by the token's gate for it (0 where the
    token did not choose it). `lp`'s expert leaves hold the held experts only."""
    ein = functools.partial(_einsum, precision)
    first = s["first_expert"] if first_expert is None else first_expert

    @jax.checkpoint
    def part(e, wg, wu, wd):
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = ein("sm,mh->sh", jax.nn.silu(ein("sh,hm->sm", u, wg)) * ein("sh,hm->sm", u, wu), wd)
        return weight[:, None] * out

    def one(z, xs):
        return z + part(*xs), None

    ids = first + jnp.arange(lp["wg"].shape[0])
    z, _ = jax.lax.scan(one, jnp.zeros_like(u), (ids, lp["wg"], lp["wu"], lp["wd"]))
    return z


def layer(s: dict, precision: str, faults: dict, x, lp: dict, pair_share):
    """One layer on a block of rows x [B, T, H]. `pair_share` [experts]: f_e of
    the whole batch, or None to count this block's pairs instead of a loss.
    Returns (x', aux term summed over the block's rows, L_I summed over the
    block's queries) or (x', pair counts [experts])."""
    b, t, hdim = x.shape
    a, kl = attention(s, precision, lp, _rms(x, lp["ln1"], s["eps"]),
                      window_fault=faults.get("window_fault", False))
    y = x + a
    u = _rms(y, lp["ln2"], s["eps"]).reshape(b * t, hdim)
    r, chosen, gates = route(s, precision, lp, u, faults.get("raw_gates", False))
    z = y + experts(s, precision, lp, u, chosen, gates).reshape(b, t, hdim)
    if pair_share is None:
        return z, jnp.sum(jax.nn.one_hot(chosen, s["experts"], dtype=F32), axis=(0, 1))
    aux = s["experts"] * jnp.sum(pair_share * jnp.mean(r, axis=0)) * b
    return z, aux, kl


def _trunk(s, precision, faults, p, ids, pair_shares):
    x = p["embed"][ids]

    @jax.checkpoint
    def body(x, xs):
        lp, share = xs
        out = layer(s, precision, faults, x, lp, share)
        return out[0], out[1:]

    return jax.lax.scan(body, x, (p["layers"], pair_shares))


def pair_counts(s: dict, precision: str, faults: dict, p: dict, block: dict):
    """[layers, experts]: the block's (token, choice) pairs by chosen expert."""
    x = p["embed"][block["input_ids"]]

    def body(x, lp):
        return layer(s, precision, faults, x, lp, None)

    return jax.lax.scan(body, x, p["layers"])[1]


def loss_sum(s: dict, precision: str, faults: dict, p: dict, block: dict, pair_shares,
             labelled: float, rows: int):
    """The block's part of the batch's loss x `rows` (the batch's row count),
    so that the blocks' parts add up to rows x loss: cross-entropy summed over
    the block's labelled positions x rows / `labelled` (the batch's count of
    them), the aux term x the block's rows, the indexer's loss likewise."""
    x, (aux, kl) = _trunk(s, precision, faults, p, block["input_ids"], pair_shares)
    b, t = block["input_ids"].shape
    x = _rms(x, p["final_norm"], s["eps"])

    def picked(_, xb, labels):
        logp = jax.nn.log_softmax(_einsum(precision, "bth,hv->btv", xb, p["head"]), axis=-1)
        at = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        return jnp.sum(jnp.where(labels >= 0, at, 0.0))

    ce = -jnp.sum(_in_blocks(picked, LOGIT_BLOCK, x, block["labels"])) * rows / labelled
    return ce + s["aux_weight"] * jnp.mean(aux) + s["indexer_weight"] * jnp.sum(kl) / t


# --------------------------------------------------------------------------
# training steps
# --------------------------------------------------------------------------

def make_step(s: dict, opt: dict, precision: str, rows_per_block: int, faults: dict,
              keep_grads: bool = False):
    """One optimizer step as a jitted function of (params, m, v, t, batch): the
    loss above with the gradient taken in blocks of `rows_per_block` rows, the
    global-norm clip, AdamW (`reference/encoder.py`'s arithmetic). Returns the
    new (params, m, v), the step's loss and gradient norm (before the clip) and,
    with `keep_grads`, the gradient (before the clip)."""
    lr, wd = float(opt["learning_rate"]), float(opt["weight_decay"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    clip = float(opt["grad_clip"])
    grad_fn = jax.value_and_grad(functools.partial(loss_sum, s, precision, faults))
    count_fn = functools.partial(pair_counts, s, precision, faults)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, batch):
        rows = batch["labels"].shape[0]
        if rows % rows_per_block:
            raise ValueError(f"{rows} rows do not divide into blocks of {rows_per_block}")
        blocks = jax.tree.map(
            lambda a: a.reshape((rows // rows_per_block, rows_per_block) + a.shape[1:]), batch)
        counts = jnp.sum(jax.lax.map(lambda blk: count_fn(p, blk), blocks), axis=0)
        shares = counts / jnp.sum(counts, axis=-1, keepdims=True)
        labelled = jnp.sum(batch["labels"] >= 0).astype(F32)

        loss, grads = 0.0, None
        for i in range(rows // rows_per_block):       # a single block at the cell's size
            part, g = grad_fn(p, jax.tree.map(lambda a: a[i], blocks), shares, labelled, rows)
            loss = loss + part
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        loss = loss / rows
        raw = grads = jax.tree.map(lambda g: g / rows, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * (clip / jnp.maximum(gnorm, clip)), grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)
        tf = t.astype(F32)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf

        def upd(w, a, b):
            return w - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * w)

        return jax.tree.map(upd, p, m, v), m, v, loss, gnorm, (raw if keep_grads else None)

    return step


def run_steps(s: dict, opt: dict, seed: int, batches: list, *,
              precision: str = "float32", rows_per_block: int = 1,
              window_fault: bool = False, raw_gates: bool = False,
              half_batch: bool = False, keep_grads: bool = False) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seed's weights
    on batches `{"input_ids", "labels"}`. Returns per-step losses and gradient
    norms and the leaves' norms (`lib/norms.py`) of the first moment and of the
    parameters' change after the last step; with `keep_grads` the first step's
    gradient (before the clip) too. `half_batch` plants the fault of a step
    that leaves out the second half of every batch's rows (as
    `reference/encoder.py` has it); the other two faults are `make_step`'s."""
    faults = {"window_fault": window_fault, "raw_gates": raw_gates}
    if half_batch:      # half the rows may not divide into the blocks asked for
        rows_per_block = math.gcd(rows_per_block, len(batches[0]["input_ids"]) // 2)
    init = jax.jit(functools.partial(init_params, s))
    p = init(fold_seed(seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = make_step(s, opt, precision, rows_per_block, faults, keep_grads)
    losses, gnorms, first_grads = [], [], None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            rows = len(batch["input_ids"]) // 2 if half_batch else None
            batch = {k: jnp.asarray(batch[k][:rows]) for k in ("input_ids", "labels")}
            p, m, v, loss, gnorm, grads = step(p, m, v, jnp.asarray(i + 1, jnp.int32), batch)
            losses.append(loss)
            gnorms.append(gnorm)
            first_grads = grads if i == 0 else first_grads
            del grads
        del v       # the seed's weights again, now that the second moment is gone
        norms = jax.jit(moment_and_change)(p, m, init(fold_seed(seed)))
    out = {"loss": [float(x) for x in losses], "grad_norm": [float(x) for x in gnorms],
           "moment_norm": {k: float(x) for k, x in norms["moment"].items()},
           "change_norm": {k: float(x) for k, x in norms["change"].items()}}
    if keep_grads:
        out["grads"] = first_grads
    return out
