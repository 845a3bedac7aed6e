"""The plain reference of a hybrid decoder: gated short-convolution layers
among full-attention layers, a dense lead layer, sigmoid-routed experts of
which one chip's share is held, embedding and head tied (LFM2-24B-A2B, cut to
one chip's share). Float32 `jax.numpy` at the highest matmul precision, its
loss, its gradient and AdamW with global-norm clipping.

It imports nothing of `synapseml_tpu` and takes nothing the program made.
Weights come from `init_params(seed)` and the routers' selection bias from
`select_bias(seed)`, in this file's own flat naming (`layer<i>.<leaf>`); the
program is handed the same through `perfbench/programs/hybrid_conv_moe_lm.py`.

Layer l, for a row x in R^{T x H} (published description: config.json of
LiquidAI/LFM2-24B-A2B, `lfm2_moe`; each size or order the source lacks is
under `assumed` in the configuration file):
  token mixer, h = RMSNorm(x):
    `layer_types[l] == "conv"`: [b, c, u] = h W_in split in three along the
      last axis, in that order; a = b * u; m[t] = sum_j w[:, j] a[t - (L-1) + j]
      with a[s] = 0 for s < 0 (tap L-1 multiplies the current position);
      x <- x + (c * m) W_out;
    `"full_attention"`: q, k, v = h Wq, h Wk, h Wv; q', k' = RoPE(RMSNorm_head(.)),
      half-split pairing, positions 0..T-1; softmax over s <= t of
      q'[t, i] . k'[s, g(i)] / sqrt(D); x <- x + concat_i(sum_s a v[s, g(i)]) Wo;
  feed-forward, f = RMSNorm(x):
    l < `num_dense_layers`: x <- x + (SiLU(f W1) * (f W3)) W2;
    else: s = sigmoid(f Wr) over ALL the model's experts; E_t = the `top_k`
      largest of s + beta (beta: the selection bias, a constant); g[t, e] =
      scale * s_e / (sum_{E_t} s + 1e-6); x <- x + sum over e in E_t held here
      of g[t, e] (SiLU(f Wg_e) * (f Wu_e)) Wd_e.
After the last layer RMSNorm and logits = x E^T over the held rows of the one
embedding matrix E. Loss = mean cross-entropy over the positions whose label
is not negative. No auxiliary term. beta gets no gradient and no update.

A batch goes in blocks of rows whose gradients add (no number of the loss is
of the whole batch); within a row, attention goes by query blocks, the
feed-forwards and the logits by position blocks, each rematerialised.

`precision`: "float32" (the reference) or "fp8" (the control: every matrix
product's operands rounded to float8_e4m3fn, one scale a tensor).
Planted faults, for the comparison's own tests and readings: `raw_gates`
leaves the routing weights unnormalised (g = s_e); `reversed_taps` applies the
taps in the opposite order (tap 0 on the current position); `no_select_bias`
chooses the experts by score alone (beta = 0).
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
TOKEN_BLOCK = 4096       # positions whose feed-forward hidden is alive at a time
LOGIT_BLOCK = 2048       # positions whose logits are alive at a time
BIAS_STD = 0.02          # the selection bias: a seeded N(0, BIAS_STD) vector a layer
FAULTS = ("raw_gates", "reversed_taps", "no_select_bias")


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


def _joined(blocks):
    """[n, B, size, ...] of `_in_blocks` back to [B, n * size, ...]."""
    out = jnp.moveaxis(blocks, 0, 1)
    return out.reshape((out.shape[0], -1) + out.shape[3:])


def sizes(config: dict) -> dict:
    """The numbers the reference needs, from a configuration file's own keys."""
    share = int(config["expert_share"].split(" of ")[0])     # "0 of 8"
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    return {"hidden": int(config["hidden_size"]), "layers": len(kinds), "kinds": kinds,
            "dense_layers": int(config["num_dense_layers"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]), "eps": float(config["norm_eps"]),
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "vocab": int(config["vocab_size"]), "taps": int(config["conv_L_cache"]),
            "dense_width": int(config["intermediate_size"]),
            "held": int(config["num_experts"]),
            "experts": int(config["published_num_experts"]),
            "first_expert": share * int(config["num_experts"]),
            "per_token": int(config["num_experts_per_tok"]),
            "expert_width": int(config["moe_intermediate_size"]),
            "gate_scale": float(config["routed_scaling_factor"])}


def has_experts(s: dict, i: int) -> bool:
    return i >= s["dense_layers"]


def param_shapes(s: dict) -> dict:
    """Flat: `layer<i>.<leaf>`, `embed`, `final_norm`."""
    h, d = s["hidden"], s["head_dim"]
    out = {"embed": (s["vocab"], h), "final_norm": (h,)}
    for i, kind in enumerate(s["kinds"]):
        if kind == "conv":
            layer = {"ln1": (h,), "w_in": (h, 3 * h), "conv": (h, s["taps"]), "w_out": (h, h)}
        else:
            layer = {"ln1": (h,), "wq": (h, s["heads"] * d), "wk": (h, s["kv_heads"] * d),
                     "wv": (h, s["kv_heads"] * d), "wo": (s["heads"] * d, h),
                     "q_norm": (d,), "k_norm": (d,)}
        layer["ln2"] = (h,)
        if has_experts(s, i):
            e, m = s["held"], s["expert_width"]
            layer.update(router=(h, s["experts"]), wg=(e, h, m), wu=(e, h, m), wd=(e, m, h))
        else:
            m = s["dense_width"]
            layer.update(w1=(h, m), w3=(h, m), w2=(m, h))
        out.update({f"layer{i}.{k}": v for k, v in layer.items()})
    return out


GAINS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def leaf_sizes(s: dict) -> dict:
    """How many numbers each leaf holds, under `lib/norms.py`'s flat names."""
    return {name: math.prod(shape) for name, shape in param_shapes(s).items()}


def init_params(s: dict, seed: int) -> dict:
    """`seed` is below 2**31 (`fold_seed`). Every leaf random from it: N(0, 0.02)
    for matrices, taps and the embedding, 1 + N(0, 0.02) for RMSNorm gains."""
    shapes = param_shapes(s)
    names = sorted(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    p = {n: 0.02 * jax.random.normal(k, shapes[n], F32) for n, k in zip(names, keys)}
    return {n: (1.0 + v if n.split(".")[-1] in GAINS else v) for n, v in p.items()}


def select_bias(s: dict, seed: int) -> dict:
    """{`layer<i>.beta`: [experts]} for the expert layers: N(0, BIAS_STD) from
    the seed, by a key of its own. A constant: nothing updates it."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0xBE7A)
    layers = [i for i in range(s["layers"]) if has_experts(s, i)]
    return {f"layer{i}.beta": BIAS_STD * jax.random.normal(k, (s["experts"],), F32)
            for i, k in zip(layers, jax.random.split(key, len(layers)))}


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x [B, T, heads, D]; half-split pairing, positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(s: dict, precision: str, lp: dict, h, reversed_taps: bool = False):
    """The conv mixer's output before the residual, for normed input h [B, T, H]."""
    ein = functools.partial(_einsum, precision)
    b, c, u = jnp.split(ein("bth,hk->btk", h, lp["w_in"]), 3, axis=-1)
    a = b * u
    taps = s["taps"]
    w = lp["conv"][:, ::-1] if reversed_taps else lp["conv"]
    m = jnp.zeros_like(a)
    for j in range(taps):
        back = taps - 1 - j              # tap j reads the position `back` before t
        shifted = a if back == 0 else jnp.concatenate(
            [jnp.zeros_like(a[:, :back]), a[:, :a.shape[1] - back]], axis=1)
        m = m + shifted * w[:, j]
    return ein("bth,hk->btk", c * m, lp["w_out"])


def attention(s: dict, precision: str, lp: dict, h, query_block: int = QUERY_BLOCK):
    """Full causal attention's output before the residual, by query blocks."""
    ein = functools.partial(_einsum, precision)
    b, t, _ = h.shape
    heads, kv, d = s["heads"], s["kv_heads"], s["head_dim"]
    q = ein("bth,hk->btk", h, lp["wq"]).reshape(b, t, heads, d)
    k = ein("bth,hk->btk", h, lp["wk"]).reshape(b, t, kv, d)
    v = ein("bth,hk->btk", h, lp["wv"]).reshape(b, t, kv, d)
    q = _rope(_rms(q, lp["q_norm"], s["eps"]), s["theta"])
    k = _rope(_rms(k, lp["k_norm"], s["eps"]), s["theta"])
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))

    def block(first, qb):
        scores = ein("bqnd,bknd->bnqk", qb, k) / math.sqrt(d)
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(qb.shape[1])[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return ein("bnqk,bknd->bqnd", probs, v)

    out = _joined(_in_blocks(block, query_block, q)).reshape(b, t, heads * d)
    return ein("btk,kh->bth", out, lp["wo"])


def dense_mlp(precision: str, lp: dict, f):
    ein = functools.partial(_einsum, precision)

    def block(_, fb):
        return ein("btm,mh->bth", jax.nn.silu(ein("bth,hm->btm", fb, lp["w1"]))
                   * ein("bth,hm->btm", fb, lp["w3"]), lp["w2"])

    return _joined(_in_blocks(block, TOKEN_BLOCK, f))


def route(s: dict, precision: str, lp: dict, beta, u, faults: dict):
    """(scores [S, experts], chosen experts [S, k], their gates [S, k]) for
    tokens u [S, H]. The choice carries no gradient; the gates do."""
    scores = jax.nn.sigmoid(_einsum(precision, "sh,he->se", u, lp["router"]))
    steer = jnp.zeros_like(beta) if faults.get("no_select_bias") else beta
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + steer), s["per_token"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if not faults.get("raw_gates"):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return scores, chosen, s["gate_scale"] * gates


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


def expert_ffn(s: dict, precision: str, lp: dict, beta, f, faults: dict):
    """The expert layer's held part for normed input f [B, T, H], by position blocks."""
    def block(_, fb):
        b, t, h = fb.shape
        u = fb.reshape(b * t, h)
        _, chosen, gates = route(s, precision, lp, beta, u, faults)
        return experts(s, precision, lp, u, chosen, gates).reshape(b, t, h)

    return _joined(_in_blocks(block, TOKEN_BLOCK, f))


def layer_params(p: dict, i: int) -> dict:
    head = f"layer{i}."
    return {k[len(head):]: v for k, v in p.items() if k.startswith(head)}


def layer(s: dict, precision: str, faults: dict, i: int, x, lp: dict, beta):
    """Layer i on a block of rows x [B, T, H]."""
    h = _rms(x, lp["ln1"], s["eps"])
    if s["kinds"][i] == "conv":
        x = x + short_conv(s, precision, lp, h, faults.get("reversed_taps", False))
    else:
        x = x + attention(s, precision, lp, h)
    f = _rms(x, lp["ln2"], s["eps"])
    if has_experts(s, i):
        return x + expert_ffn(s, precision, lp, beta, f, faults)
    return x + dense_mlp(precision, lp, f)


def loss_sum(s: dict, precision: str, faults: dict, p: dict, bias: dict, block: dict):
    """The cross-entropy SUMMED over the block's labelled positions."""
    x = p["embed"][block["input_ids"]]
    for i in range(s["layers"]):
        x = jax.checkpoint(functools.partial(layer, s, precision, faults, i))(
            x, layer_params(p, i), bias.get(f"layer{i}.beta"))
    x = _rms(x, p["final_norm"], s["eps"])

    def picked(_, xb, labels):
        logp = jax.nn.log_softmax(_einsum(precision, "bth,vh->btv", xb, p["embed"]), axis=-1)
        at = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        return jnp.sum(jnp.where(labels >= 0, at, 0.0))

    return -jnp.sum(_in_blocks(picked, LOGIT_BLOCK, x, block["labels"]))


# --------------------------------------------------------------------------
# training steps
# --------------------------------------------------------------------------

def make_step(s: dict, opt: dict, precision: str, rows_per_block: int, faults: dict,
              keep_grads: bool = False):
    """One optimizer step as a jitted function of (params, m, v, t, bias, batch):
    the loss above with the gradient taken in blocks of `rows_per_block` rows,
    the global-norm clip, AdamW (`reference/encoder.py`'s arithmetic). Returns
    the new (params, m, v), the step's loss and gradient norm (before the clip)
    and, with `keep_grads`, the gradient (before the clip)."""
    lr, wd = float(opt["learning_rate"]), float(opt["weight_decay"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    clip = float(opt["grad_clip"])
    grad_fn = jax.value_and_grad(functools.partial(loss_sum, s, precision, faults))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, bias, batch):
        rows = batch["labels"].shape[0]
        if rows % rows_per_block:
            raise ValueError(f"{rows} rows do not divide into blocks of {rows_per_block}")
        labelled = jnp.sum(batch["labels"] >= 0).astype(F32)
        loss, grads = 0.0, None
        for i in range(0, rows, rows_per_block):      # a single block at the cell's size
            part, g = grad_fn(p, bias, jax.tree.map(lambda a: a[i:i + rows_per_block], batch))
            loss = loss + part
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        loss = loss / labelled
        raw = grads = jax.tree.map(lambda g: g / labelled, grads)
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
              raw_gates: bool = False, reversed_taps: bool = False,
              no_select_bias: bool = False, keep_grads: bool = False) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seed's weights
    on batches `{"input_ids", "labels"}`. Returns per-step losses and gradient
    norms and the leaves' norms (`lib/norms.py`) of the first moment and of the
    parameters' change after the last step; with `keep_grads` the first step's
    gradient (before the clip) too. The faults are the module docstring's."""
    faults = {"raw_gates": raw_gates, "reversed_taps": reversed_taps,
              "no_select_bias": no_select_bias}
    init = jax.jit(functools.partial(init_params, s))
    p = init(fold_seed(seed))
    bias = jax.jit(functools.partial(select_bias, s))(fold_seed(seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = make_step(s, opt, precision, rows_per_block, faults, keep_grads)
    losses, gnorms, first_grads = [], [], None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            batch = {k: jnp.asarray(batch[k]) for k in ("input_ids", "labels")}
            p, m, v, loss, gnorm, grads = step(p, m, v, jnp.asarray(i + 1, jnp.int32),
                                               bias, batch)
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
