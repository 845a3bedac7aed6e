"""The plain reference of a latent-attention decoder: multi-head latent
attention in every layer, a dense lead layer, sigmoid-routed experts of which
one chip's share is held beside a shared expert that every token passes
through, an untied head (Moonlight-16B-A3B, cut to one chip's share). Float32
`jax.numpy` at the highest matmul precision, its loss, its gradient and AdamW
with global-norm clipping.

It imports nothing of `synapseml_tpu` and takes nothing the program made.
Weights come from `init_params(seed)` and the routers' selection bias from
`select_bias(seed)`, in this file's own flat naming (`layer<i>.<leaf>`); the
program is handed the same through `perfbench/programs/latent_moe_lm.py`. The
pieces it shares with `reference/hybrid_conv_moe_lm.py` (RMSNorm, RoPE, the
dense gated MLP, the held experts' part, the blocking helpers, the selection
bias's draw) are imported from there, not copied.

Layer l, for a row x in R^{T x H} (published description: config.json of
moonshotai/Moonlight-16B-A3B, `deepseek_v3`; each size or order the source
lacks is under `assumed` in the configuration file). N = `qk_nope_head_dim`,
R = `qk_rope_head_dim`, V = `v_head_dim`, L = `kv_lora_rank`:
  token mixer, h = RMSNorm(x):
    q = h Wq in [T, heads, N + R]; q_n = q[..., :N], q_r = q[..., N:];
    c = h Wa in [T, L + R]; latent z = c[:, :L]; k_r = c[:, L:], ONE rotary key
      a position, shared by all heads;
    u = RMSNorm_L(z) Wb in [T, heads, N + V]; k_n = u[..., :N], v = u[..., N:];
    q_r', k_r' = RoPE(q_r), RoPE(k_r): R dims, half-split pairing, positions
      0..T-1; q' = [q_n, q_r'], k'[s, i] = [k_n[s, i], k_r'[s]] in R^{N + R};
    a = softmax over s <= t of q'[t, i] . k'[s, i] / sqrt(N + R);
    x <- x + concat_i(sum_s a[t, i, s] v[s, i]) Wo;
  feed-forward, f = RMSNorm(x):
    l < `first_k_dense_replace`: x <- x + (SiLU(f W1) * (f W3)) W2;
    else: s = sigmoid(f Wr) over ALL the model's experts; E_t = the `top_k`
      largest of s + beta (beta: the selection bias, a constant); g[t, e] =
      scale * s_e / (sum_{E_t} s + 1e-20); x <- x + sum over e in E_t held
      here of g[t, e] (SiLU(f Wg_e) * (f Wu_e)) Wd_e + (SiLU(f Sg) * (f Su)) Sd,
      the last term the shared expert, for every token.
After the last layer RMSNorm and logits = x W_head over the held vocabulary
rows; the embedding is its own matrix. Loss = mean cross-entropy over the
positions whose label is not negative. No auxiliary term. beta gets no
gradient and no update.

A batch goes in blocks of rows whose gradients add; within a row, attention
goes by query blocks, the feed-forwards and the logits by position blocks,
each rematerialised.

`precision`: "float32" (the reference) or "fp8" (the control: every matrix
product's operands rounded to float8_e4m3fn, one scale a tensor).
Planted faults, for the comparison's own tests and readings: `raw_gates`
leaves the routing weights neither normalised nor scaled (g = s_e);
`no_shared` leaves the shared expert out; `rope_everywhere` turns all N + R
dims of every query and key (one table over N + R); `no_latent_norm` feeds
the up-projection the latent as it is; `half_batch` leaves the second half of
every batch's rows out of the step and takes the mean over the rest (as
`reference/encoder.py` has it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.lib.norms import moment_and_change
from perfbench.reference.encoder import _einsum, fold_seed
from perfbench.reference.hybrid_conv_moe_lm import (_in_blocks, _joined, _rms, _rope,
                                                    dense_mlp, experts, layer_params,
                                                    select_bias)

F32 = jnp.float32
QUERY_BLOCK = 256        # queries whose scores are alive at a time, where they divide T
TOKEN_BLOCK = 4096       # positions whose expert hidden is alive at a time
LOGIT_BLOCK = 2048       # positions whose logits are alive at a time
GATE_EPS = 1e-20         # in the denominator of the gates' normalisation
FAULTS = ("raw_gates", "no_shared", "rope_everywhere", "no_latent_norm", "half_batch")
GAINS = ("ln1", "ln2", "kv_norm", "final_norm")


def sizes(config: dict) -> dict:
    """The numbers the reference needs, from a configuration file's own keys."""
    share = int(config["expert_share"].split(" of ")[0])     # "0 of 8"
    return {"hidden": int(config["hidden_size"]), "layers": int(config["num_hidden_layers"]),
            "dense_layers": int(config["first_k_dense_replace"]),
            "heads": int(config["num_attention_heads"]),
            "nope": int(config["qk_nope_head_dim"]), "rope": int(config["qk_rope_head_dim"]),
            "value": int(config["v_head_dim"]), "latent": int(config["kv_lora_rank"]),
            "eps": float(config["rms_norm_eps"]), "theta": float(config["rope_theta"]),
            "vocab": int(config["vocab_size"]),
            "dense_width": int(config["intermediate_size"]),
            "held": int(config["n_routed_experts"]),
            "experts": int(config["published_n_routed_experts"]),
            "first_expert": share * int(config["n_routed_experts"]),
            "per_token": int(config["num_experts_per_tok"]),
            "expert_width": int(config["moe_intermediate_size"]),
            "shared_width": int(config["n_shared_experts"]) * int(config["moe_intermediate_size"]),
            "gate_scale": float(config["routed_scaling_factor"])}


def has_experts(s: dict, i: int) -> bool:
    return i >= s["dense_layers"]


def param_shapes(s: dict) -> dict:
    """Flat: `layer<i>.<leaf>`, `embed`, `head`, `final_norm`."""
    h, heads = s["hidden"], s["heads"]
    n, r, v, lat = s["nope"], s["rope"], s["value"], s["latent"]
    out = {"embed": (s["vocab"], h), "head": (h, s["vocab"]), "final_norm": (h,)}
    for i in range(s["layers"]):
        layer = {"ln1": (h,), "wq": (h, heads * (n + r)), "wa": (h, lat + r), "kv_norm": (lat,),
                 "wb": (lat, heads * (n + v)), "wo": (heads * v, h), "ln2": (h,)}
        if has_experts(s, i):
            e, m, sw = s["held"], s["expert_width"], s["shared_width"]
            layer.update(router=(h, s["experts"]), wg=(e, h, m), wu=(e, h, m), wd=(e, m, h),
                         sg=(h, sw), su=(h, sw), sd=(sw, h))
        else:
            m = s["dense_width"]
            layer.update(w1=(h, m), w3=(h, m), w2=(m, h))
        out.update({f"layer{i}.{k}": shape for k, shape in layer.items()})
    return out


def leaf_sizes(s: dict) -> dict:
    """How many numbers each leaf holds, under `lib/norms.py`'s flat names."""
    return {name: math.prod(shape) for name, shape in param_shapes(s).items()}


def init_params(s: dict, seed: int) -> dict:
    """`seed` is below 2**31 (`fold_seed`). Every leaf random from it: N(0, 0.02)
    for matrices and the embedding, 1 + N(0, 0.02) for RMSNorm gains."""
    shapes = param_shapes(s)
    names = sorted(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    p = {n: 0.02 * jax.random.normal(k, shapes[n], F32) for n, k in zip(names, keys)}
    return {n: (1.0 + v if n.split(".")[-1] in GAINS else v) for n, v in p.items()}


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def attention(s: dict, precision: str, lp: dict, h, faults: dict | None = None):
    """Latent attention's output before the residual, by query blocks."""
    faults = faults or {}
    ein = functools.partial(_einsum, precision)
    b, t, _ = h.shape
    heads, n, r, v_dim, lat = s["heads"], s["nope"], s["rope"], s["value"], s["latent"]
    q = ein("bth,hk->btk", h, lp["wq"]).reshape(b, t, heads, n + r)
    c = ein("bth,hk->btk", h, lp["wa"])
    z = c[..., :lat] if faults.get("no_latent_norm") else _rms(c[..., :lat], lp["kv_norm"],
                                                               s["eps"])
    u = ein("btl,lk->btk", z, lp["wb"]).reshape(b, t, heads, n + v_dim)
    k_rot = jnp.broadcast_to(c[:, :, None, lat:], (b, t, heads, r))
    if faults.get("rope_everywhere"):
        q = _rope(q, s["theta"])
        k = _rope(jnp.concatenate([u[..., :n], k_rot], axis=-1), s["theta"])
    else:
        q = jnp.concatenate([q[..., :n], _rope(q[..., n:], s["theta"])], axis=-1)
        k = jnp.concatenate([u[..., :n], _rope(k_rot, s["theta"])], axis=-1)
    v = u[..., n:]

    def block(first, qb):
        scores = ein("bqnd,bknd->bnqk", qb, k) / math.sqrt(n + r)
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(qb.shape[1])[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return ein("bnqk,bknd->bqnd", probs, v)

    out = _joined(_in_blocks(block, QUERY_BLOCK, q)).reshape(b, t, heads * v_dim)
    return ein("btk,kh->bth", out, lp["wo"])


def route(s: dict, precision: str, lp: dict, beta, u, faults: dict):
    """(scores [S, experts], chosen experts [S, k], their gates [S, k]) for
    tokens u [S, H]: normalised, then scaled. The choice carries no gradient;
    the gates do."""
    scores = jax.nn.sigmoid(_einsum(precision, "sh,he->se", u, lp["router"]))
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + beta), s["per_token"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if faults.get("raw_gates"):
        return scores, chosen, gates
    return scores, chosen, s["gate_scale"] * gates / (
        jnp.sum(gates, axis=-1, keepdims=True) + GATE_EPS)


def shared_expert(precision: str, lp: dict, f):
    """The shared expert's result for every token of f [B, T, H]."""
    return dense_mlp(precision, {"w1": lp["sg"], "w3": lp["su"], "w2": lp["sd"]}, f)


def expert_ffn(s: dict, precision: str, lp: dict, beta, f, faults: dict):
    """The expert layer's result for normed input f [B, T, H]: the held routed
    experts' part by position blocks, plus the shared expert."""
    def block(_, fb):
        b, t, h = fb.shape
        u = fb.reshape(b * t, h)
        _, chosen, gates = route(s, precision, lp, beta, u, faults)
        return experts(s, precision, lp, u, chosen, gates).reshape(b, t, h)

    routed = _joined(_in_blocks(block, TOKEN_BLOCK, f))
    return routed if faults.get("no_shared") else routed + shared_expert(precision, lp, f)


def layer(s: dict, precision: str, faults: dict, i: int, x, lp: dict, beta):
    """Layer i on a block of rows x [B, T, H]."""
    x = x + attention(s, precision, lp, _rms(x, lp["ln1"], s["eps"]), faults)
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
        logp = jax.nn.log_softmax(_einsum(precision, "bth,hv->btv", xb, p["head"]), axis=-1)
        at = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        return jnp.sum(jnp.where(labels >= 0, at, 0.0))

    return -jnp.sum(_in_blocks(picked, LOGIT_BLOCK, x, block["labels"]))


# --------------------------------------------------------------------------
# training steps
# --------------------------------------------------------------------------

def make_step(loss_fn, opt: dict, rows_per_block: int, keep_grads: bool = False):
    """One optimizer step as a jitted function of (params, m, v, t, bias, batch)
    for `loss_fn(params, bias, block)`, a loss SUMMED over the block's labelled
    positions: its gradient in blocks of `rows_per_block` rows, the global-norm
    clip, AdamW (`reference/encoder.py`'s arithmetic). Returns the new
    (params, m, v), the step's loss and gradient norm (before the clip) and,
    with `keep_grads`, the gradient (before the clip)."""
    lr, wd = float(opt["learning_rate"]), float(opt["weight_decay"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    clip = float(opt["grad_clip"])
    grad_fn = jax.value_and_grad(loss_fn)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, bias, batch):
        rows = batch["labels"].shape[0]
        if rows % rows_per_block:
            raise ValueError(f"{rows} rows do not divide into blocks of {rows_per_block}")
        labelled = jnp.sum(batch["labels"] >= 0).astype(F32)
        loss, grads = 0.0, None
        for i in range(0, rows, rows_per_block):
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
              keep_grads: bool = False, **faults) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seed's weights
    on batches `{"input_ids", "labels"}`. Returns per-step losses and gradient
    norms and the leaves' norms (`lib/norms.py`) of the first moment and of the
    parameters' change after the last step; with `keep_grads` the first step's
    gradient (before the clip) too. `faults`: the module docstring's, by name."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"no planted fault {sorted(unknown)}; has {FAULTS}")
    half = bool(faults.get("half_batch"))
    if half:            # half the rows may not divide into the blocks asked for
        rows_per_block = math.gcd(rows_per_block, len(batches[0]["input_ids"]) // 2)
    init = jax.jit(functools.partial(init_params, s))
    p = init(fold_seed(seed))
    bias = jax.jit(functools.partial(select_bias, s))(fold_seed(seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = make_step(functools.partial(loss_sum, s, precision, faults), opt, rows_per_block,
                     keep_grads)
    losses, gnorms, first_grads = [], [], None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            rows = len(batch["input_ids"]) // 2 if half else None
            batch = {k: jnp.asarray(batch[k][:rows]) for k in ("input_ids", "labels")}
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
