"""The plain reference: one float32 `jax.numpy` transformer encoder with two
embedders (token ids, image patches), its classification loss, its gradient
and AdamW with global-norm clipping.

It imports nothing of `synapseml_tpu` and takes nothing the program made.
Weights come from `init_params(seed)`, in this file's own naming; the
program is handed the same weights through `perfbench/programs/<name>.py`.

Model description followed (departures noted where they occur):
  * BERT (Devlin et al. 2018; `google-bert/bert-base-uncased`): word +
    position + segment embeddings, LayerNorm, post-norm blocks
    (x = LN(x + attn(x)); x = LN(x + mlp(x))), exact-erf GELU, tanh pooler on
    token 0, linear classifier. Dropout is off (the program's trainer passes
    no dropout rng).
  * ViT (Dosovitskiy et al. 2020; `google/vit-base-patch16-224`): 16x16
    patch embedding as one matmul over flattened (row, column, channel)
    patches, a learned class token, learned position embeddings, pre-norm
    blocks, a final LayerNorm, linear head on token 0.

`precision` selects the arithmetic of every matrix product:
  * "float32": operands as they are, `lax.Precision.HIGHEST` (the reference);
  * "fp8": both operands rounded to float8_e4m3fn with one scale per tensor
    (the control: the nearest precision below the bfloat16 the configurations
    state).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.lib.norms import moment_and_change

F32 = jnp.float32


# --------------------------------------------------------------------------
# sizes and weights
# --------------------------------------------------------------------------

def sizes(config: dict) -> dict:
    """The numbers the reference needs, read from a configuration file's own
    (Hugging Face) keys."""
    s = {"kind": config["inputs"], "hidden": int(config["hidden_size"]),
         "layers": int(config["num_hidden_layers"]),
         "heads": int(config["num_attention_heads"]),
         "mlp": int(config["intermediate_size"]),
         "eps": float(config["layer_norm_eps"]),
         "classes": int(config["num_labels"])}
    if s["kind"] == "text":
        s.update(vocab=int(config["vocab_size"]),
                 positions=int(config["max_position_embeddings"]),
                 segments=int(config["type_vocab_size"]), prenorm=False)
    elif s["kind"] == "image":
        s.update(patch=int(config["patch_size"]), image=int(config["image_size"]),
                 channels=int(config["num_channels"]), prenorm=True)
        s["tokens"] = 1 + (s["image"] // s["patch"]) ** 2
    else:
        raise ValueError(f"unknown inputs kind {s['kind']!r}")
    return s


def fold_seed(seed: int) -> int:
    """`--seed` may pass 2**31; a PRNG key takes 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def param_shapes(s: dict) -> dict:
    h, m, n = s["hidden"], s["mlp"], s["layers"]
    layer = {"wq": (h, h), "bq": (h,), "wk": (h, h), "bk": (h,),
             "wv": (h, h), "bv": (h,), "wo": (h, h), "bo": (h,),
             "ln1_g": (h,), "ln1_b": (h,), "w1": (h, m), "b1": (m,),
             "w2": (m, h), "b2": (h,), "ln2_g": (h,), "ln2_b": (h,)}
    shapes = {"layers": {k: (n,) + v for k, v in layer.items()},
              "head_w": (h, s["classes"]), "head_b": (s["classes"],)}
    if s["kind"] == "text":
        shapes.update(word=(s["vocab"], h), position=(s["positions"], h),
                      segment=(s["segments"], h), emb_ln_g=(h,), emb_ln_b=(h,),
                      pool_w=(h, h), pool_b=(h,))
    else:
        shapes.update(patch_w=(s["patch"] * s["patch"] * s["channels"], h),
                      patch_b=(h,), cls=(h,), pos=(s["tokens"], h),
                      final_ln_g=(h,), final_ln_b=(h,))
    return shapes


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
    """`seed` is below 2**31 (see `fold_seed`). Every leaf random from it, so that a leaf the program leaves
    unmoved or mixes up shows: N(0, 0.02) for matrices, embeddings and
    biases, 1 + N(0, 0.02) for LayerNorm gains."""
    shapes = param_shapes(s)
    flat, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    leaves = [0.02 * jax.random.normal(k, shp, F32) for k, shp in zip(keys, flat)]
    p = jax.tree.unflatten(treedef, leaves)
    for name in ("ln1_g", "ln2_g"):
        p["layers"][name] = 1.0 + p["layers"][name]
    for name in ("emb_ln_g", "final_ln_g"):
        if name in p:
            p[name] = 1.0 + p[name]
    return p


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------

def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    # straight-through: the rounding has no gradient of its own
    return x + jax.lax.stop_gradient(q - x)


def _einsum(precision: str, spec: str, a, b):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _block(s: dict, precision: str, x, lp: dict, key_mask):
    ein = functools.partial(_einsum, precision)
    heads, dim = s["heads"], s["hidden"] // s["heads"]

    def attend(y):
        b, t, _ = y.shape
        q = (ein("bth,hk->btk", y, lp["wq"]) + lp["bq"]).reshape(b, t, heads, dim)
        k = (ein("bth,hk->btk", y, lp["wk"]) + lp["bk"]).reshape(b, t, heads, dim)
        v = (ein("bth,hk->btk", y, lp["wv"]) + lp["bv"]).reshape(b, t, heads, dim)
        scores = ein("bqnd,bknd->bnqk", q, k) / math.sqrt(dim)
        if key_mask is not None:
            scores = jnp.where(key_mask[:, None, None, :], scores,
                               jnp.finfo(F32).min)
        probs = jax.nn.softmax(scores, axis=-1)
        out = ein("bnqk,bknd->bqnd", probs, v).reshape(b, t, heads * dim)
        return ein("bth,hk->btk", out, lp["wo"]) + lp["bo"]

    def mlp(y):
        up = _gelu(ein("bth,hm->btm", y, lp["w1"]) + lp["b1"])
        return ein("btm,mh->bth", up, lp["w2"]) + lp["b2"]

    if s["prenorm"]:
        x = x + attend(_layer_norm(x, lp["ln1_g"], lp["ln1_b"], s["eps"]))
        return x + mlp(_layer_norm(x, lp["ln2_g"], lp["ln2_b"], s["eps"]))
    x = _layer_norm(x + attend(x), lp["ln1_g"], lp["ln1_b"], s["eps"])
    return _layer_norm(x + mlp(x), lp["ln2_g"], lp["ln2_b"], s["eps"])


def logits_fn(s: dict, precision: str, p: dict, batch: dict):
    """[rows, classes] float32 logits of a batch in the harness's own column
    names: `input_ids` + `attention_mask`, or `x` ([rows, H, W, C] pixels)."""
    ein = functools.partial(_einsum, precision)
    if s["kind"] == "text":
        ids = batch["input_ids"]
        t = ids.shape[1]
        x = p["word"][ids] + p["position"][:t][None] + p["segment"][0][None, None]
        x = _layer_norm(x, p["emb_ln_g"], p["emb_ln_b"], s["eps"])
        key_mask = batch["attention_mask"].astype(bool)
    else:
        img = batch["x"].astype(F32)
        b, hh, ww, c = img.shape
        ps = s["patch"]
        patches = img.reshape(b, hh // ps, ps, ww // ps, ps, c)
        patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, (hh // ps) * (ww // ps), ps * ps * c)
        x = ein("btk,kh->bth", patches, p["patch_w"]) + p["patch_b"]
        cls = jnp.broadcast_to(p["cls"][None, None], (b, 1, s["hidden"]))
        x = jnp.concatenate([cls, x], axis=1) + p["pos"][None]
        key_mask = None

    def body(x, lp):
        return _block(s, precision, x, lp, key_mask), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    if s["kind"] == "text":
        pooled = jnp.tanh(ein("bh,hk->bk", x[:, 0], p["pool_w"]) + p["pool_b"])
    else:
        pooled = _layer_norm(x, p["final_ln_g"], p["final_ln_b"], s["eps"])[:, 0]
    return ein("bh,hc->bc", pooled, p["head_w"]) + p["head_b"]


def loss_sum(s: dict, precision: str, p: dict, batch: dict):
    """Sum (not mean) of the rows' cross-entropies, so that blocks of rows add."""
    logp = jax.nn.log_softmax(logits_fn(s, precision, p, batch), axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][:, None].astype(jnp.int32),
                                 axis=-1)
    return -jnp.sum(picked)


# --------------------------------------------------------------------------
# training steps
# --------------------------------------------------------------------------

def make_step(s: dict, opt: dict, precision: str, rows_per_block: int):
    """One optimizer step as a jitted function of (params, m, v, t, batch):
    mean cross-entropy over the batch, the gradient taken in blocks of
    `rows_per_block` rows so that float32 activations fit beside the state,
    the global-norm clip, AdamW. Returns the new (params, m, v) and the
    step's loss and gradient norm (before the clip)."""
    lr, wd = float(opt["learning_rate"]), float(opt["weight_decay"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    clip = float(opt["grad_clip"])
    grad_fn = jax.value_and_grad(functools.partial(loss_sum, s, precision))

    @jax.jit
    def step(p, m, v, t, batch):
        rows = batch["labels"].shape[0]
        if rows % rows_per_block:
            raise ValueError(f"{rows} rows do not divide into blocks of "
                             f"{rows_per_block}")
        blocks = jax.tree.map(
            lambda a: a.reshape((rows // rows_per_block, rows_per_block)
                                + a.shape[1:]), batch)

        def add(carry, block):
            loss, grads = grad_fn(p, block)
            return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], grads)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, p))
        (loss, grads), _ = jax.lax.scan(add, zero, blocks)
        loss = loss / rows
        grads = jax.tree.map(lambda g: g / rows, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * (clip / jnp.maximum(gnorm, clip)),
                             grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)
        tf = t.astype(F32)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf

        def upd(w, a, b):
            return w - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * w)

        return jax.tree.map(upd, p, m, v), m, v, loss, gnorm

    return step


def run_steps(s: dict, opt: dict, seed: int, batches: list, *,
              precision: str = "float32", rows_per_block: int = 32,
              half_batch: bool = False) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seed's
    weights. Returns per-step losses and gradient norms and the leaves'
    norms (`leaf_norms`) of the first moment and of the parameters' change
    after the last step. `half_batch` plants the fault of a step that leaves
    out the second half of every batch and takes the mean over the rest."""
    init = jax.jit(functools.partial(init_params, s))
    p0 = init(fold_seed(seed))
    p = init(fold_seed(seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = make_step(s, opt, precision, rows_per_block)
    losses, gnorms = [], []
    for i, batch in enumerate(batches):
        if half_batch:
            batch = {k: a[: a.shape[0] // 2] for k, a in batch.items()}
        p, m, v, loss, gnorm = step(p, m, v, jnp.asarray(i + 1, jnp.int32), batch)
        losses.append(loss)
        gnorms.append(gnorm)
    norms = jax.jit(moment_and_change)(p, m, p0)
    return {"loss": [float(x) for x in losses],
            "grad_norm": [float(x) for x in gnorms],
            "moment_norm": {k: float(x) for k, x in norms["moment"].items()},
            "change_norm": {k: float(x) for k, x in norms["change"].items()}}
