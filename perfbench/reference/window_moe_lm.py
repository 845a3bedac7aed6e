"""The plain reference of a decoder of sliding-window and full attention
layers: full layers of fewer query heads that turn part of each head under
YaRN's frequencies, sliding layers of more heads that see a window of keys and
turn every dim, a sigmoid gate a head on both, a dense lead layer, softmax-
routed experts of which one chip's share is held beside a shared expert that
every token passes through, an untied head (Laguna-XS.2, cut to one chip's
share). Float32 `jax.numpy` at the highest matmul precision, its loss, its
gradient and AdamW with global-norm clipping.

It imports nothing of `synapseml_tpu` and takes nothing the program made.
Weights come from `init_params(seed)`, in this file's own flat naming
(`layer<i>.<leaf>`); the program is handed the same through
`perfbench/programs/window_moe_lm.py`. RMSNorm, the dense gated MLP, the held
experts' part and the blocking helpers are imported from
`reference/hybrid_conv_moe_lm.py`, the training step (gradient in row blocks,
the clip, AdamW) from `reference/latent_moe_lm.py`, not copied.

Layer l, for a row x in R^{T x H} (published description: config.json of
poolside/Laguna-XS.2, `laguna`; each size or form the source lacks is under
`assumed` in the configuration file). Its kind k is `layer_types[l]`, its
query heads n = `num_attention_heads_per_layer[l]`, D = `head_dim`, G =
`num_key_value_heads`:
  token mixer, u = RMSNorm(x):
    q = u Wq in [T, n, D]; k = u Wk, v = u Wv in [T, G, D]; head i reads key
      and value head i // (n / G);
    RoPE by kind (`rope_parameters[k]`): the leading r = D x
      `partial_rotary_factor` dims of q and k turn, half-split pairing among
      themselves, positions 0..T-1, the other D - r pass; inverse
      frequencies theta^(-2j / r), under YaRN (`rope_type` "yarn") the ramp
      of `transformers`' `_compute_yarn_parameters` between the dims that
      turn `beta_fast` and `beta_slow` times over
      `original_max_position_embeddings` positions (truncated to whole dims),
      interpolated by 1 / `factor` below it, and cos and sin x
      `attention_factor`;
    s[t, j] = q'[t, i] . k'[j, i] / sqrt(D) over the keys j <= t, and on a
      sliding layer t - W < j <= t (W = `sliding_window`: the key itself and
      the W - 1 before it); a = softmax_j(s) v;
    g[t, i] = sigmoid(u[t] . Wg[:, i]), one gate a head;
    x <- x + concat_i(g[t, i] a[t, i]) Wo;
  feed-forward, f = RMSNorm(x):
    `mlp_layer_types[l]` "dense": x <- x + (SiLU(f W1) * (f W3)) W2;
    "sparse": p = softmax(f Wr) over ALL the model's experts; E_t = the
      `num_experts_per_tok` largest; g[t, e] = `moe_routed_scaling_factor` x
      p_e / sum_{E_t} p; x <- x + sum over e in E_t held here of g[t, e]
      (SiLU(f Wg_e) * (f Wu_e)) Wd_e + (SiLU(f Sg) * (f Su)) Sd, the last term
      the shared expert, for every token.
After the last layer RMSNorm and logits = x W_head over the held vocabulary
rows; the embedding is its own matrix. Loss = mean cross-entropy over the
positions whose label is not negative. No auxiliary term.

A batch goes in blocks of rows whose gradients add; within a row, attention
goes by query blocks (every key of the row under the mask built from
positions), the feed-forwards and the logits by position blocks, each
rematerialised.

`precision`: "float32" (the reference) or "fp8" (the control: every matrix
product's operands rounded to float8_e4m3fn, one scale a tensor).
Planted faults, for the comparison's own tests and readings: `no_window`
lets the sliding layers see every causal key; `no_head_gate` leaves the
heads' gates out; `plain_rope` turns the full layers' rotary dims at their
theta without YaRN (no ramp, no factor); `raw_gates` leaves the routing
weights neither normalised nor scaled (g = p_e); `no_shared` leaves the
shared expert out; `half_batch` leaves the second half of every batch's rows
out of the step and takes the mean over the rest.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.lib.norms import moment_and_change
from perfbench.reference.encoder import _einsum, fold_seed
from perfbench.reference.hybrid_conv_moe_lm import (_in_blocks, _joined, _rms, dense_mlp,
                                                    experts, layer_params)
from perfbench.reference.latent_moe_lm import make_step

F32 = jnp.float32
QUERY_BLOCK = 128        # queries whose scores are alive at a time, where they divide T
TOKEN_BLOCK = 4096       # positions whose expert hidden is alive at a time
LOGIT_BLOCK = 2048       # positions whose logits are alive at a time
FAULTS = ("no_window", "no_head_gate", "plain_rope", "raw_gates", "no_shared", "half_batch")
GAINS = ("ln1", "ln2", "final_norm")


def sizes(config: dict) -> dict:
    """The numbers the reference needs, from a configuration file's own keys."""
    share = int(config["expert_share"].split(" of ")[0])     # "0 of 8"
    layers = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])
    heads = tuple(int(n) for n in config["num_attention_heads_per_layer"])
    sparse = tuple(kind == "sparse" for kind in config["mlp_layer_types"])
    if not len(kinds) == len(heads) == len(sparse) == layers:
        raise ValueError("the per-layer lists and num_hidden_layers disagree")
    rope = {kind: dict(config["rope_parameters"][kind]) for kind in set(kinds)}
    return {"hidden": int(config["hidden_size"]), "layers": layers, "kinds": kinds,
            "heads": heads, "sparse": sparse,
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]), "eps": float(config["rms_norm_eps"]),
            "window": int(config["sliding_window"]), "rope": rope,
            "vocab": int(config["vocab_size"]),
            "dense_width": int(config["intermediate_size"]),
            "held": int(config["num_experts"]),
            "experts": int(config["published_num_experts"]),
            "first_expert": share * int(config["num_experts"]),
            "per_token": int(config["num_experts_per_tok"]),
            "expert_width": int(config["moe_intermediate_size"]),
            "shared_width": int(config["shared_expert_intermediate_size"]),
            "gate_scale": float(config["moe_routed_scaling_factor"])}


def param_shapes(s: dict) -> dict:
    """Flat: `layer<i>.<leaf>`, `embed`, `head`, `final_norm`."""
    h, d, g = s["hidden"], s["head_dim"], s["kv_heads"]
    out = {"embed": (s["vocab"], h), "head": (h, s["vocab"]), "final_norm": (h,)}
    for i in range(s["layers"]):
        n = s["heads"][i]
        layer = {"ln1": (h,), "wq": (h, n * d), "wk": (h, g * d), "wv": (h, g * d),
                 "head_gate": (h, n), "wo": (n * d, h), "ln2": (h,)}
        if s["sparse"][i]:
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


def select_bias(s: dict, seed: int) -> dict:
    """This family's router has no selection bias: no constants
    (`drivers/latent_lm_train_window.py` hands the state what this returns)."""
    return {}


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def _rotary(x, rope: dict, plain: bool = False):
    """x [B, T, heads, D] with its leading D x `partial_rotary_factor` dims
    turned (half-split pairing among themselves, positions 0..T-1) by the
    kind's `rope_parameters`; `plain` leaves YaRN out."""
    t, d = x.shape[1], x.shape[-1]
    r = int(d * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    factor = 1.0
    if rope["rope_type"] == "yarn" and not plain:
        original = float(rope["original_max_position_embeddings"])

        def dim_of(turns):      # the dim whose angle turns `turns` times over `original`
            return r * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
        high = min(math.ceil(dim_of(float(rope["beta_slow"]))), r - 1)
        ramp = jnp.clip((jnp.arange(r // 2, dtype=F32) - low) / max(high - low, 0.001), 0.0, 1.0)
        inv = inv / float(rope["factor"]) * ramp + inv * (1.0 - ramp)
        factor = float(rope["attention_factor"])
    elif rope["rope_type"] not in ("default", "yarn"):
        raise ValueError(f"no rotary of type {rope['rope_type']!r}")
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = (factor * jnp.cos(angle))[None, :, None, :]
    sin = (factor * jnp.sin(angle))[None, :, None, :]
    x1, x2 = jnp.split(x[..., :r], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def attention(s: dict, precision: str, i: int, lp: dict, u, faults: dict | None = None):
    """Layer i's gated attention output before the residual, by query blocks."""
    faults = faults or {}
    ein = functools.partial(_einsum, precision)
    b, t, _ = u.shape
    kind, n, g, d = s["kinds"][i], s["heads"][i], s["kv_heads"], s["head_dim"]
    q = ein("bth,hk->btk", u, lp["wq"]).reshape(b, t, n, d)
    k = ein("bth,hk->btk", u, lp["wk"]).reshape(b, t, g, d)
    v = ein("bth,hk->btk", u, lp["wv"]).reshape(b, t, g, d)
    plain = bool(faults.get("plain_rope"))
    q, k = _rotary(q, s["rope"][kind], plain), _rotary(k, s["rope"][kind], plain)
    q = q.reshape(b, t, g, n // g, d)           # head i = (i // (n / G), i % (n / G))
    window = s["window"] if kind == "sliding_attention" and not faults.get("no_window") else 0

    def block(first, qb):
        scores = ein("bqgrd,bkgd->bgrqk", qb, k) / math.sqrt(d)
        query = first + jnp.arange(qb.shape[1])[:, None]
        key = jnp.arange(t)[None, :]
        seen = key <= query
        if window:
            seen = seen & (key > query - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return ein("bgrqk,bkgd->bqgrd", probs, v)

    a = _joined(_in_blocks(block, QUERY_BLOCK, q)).reshape(b, t, n, d)
    if not faults.get("no_head_gate"):
        a = a * jax.nn.sigmoid(ein("bth,hn->btn", u, lp["head_gate"]))[..., None]
    return ein("btk,kh->bth", a.reshape(b, t, n * d), lp["wo"])


def route(s: dict, precision: str, lp: dict, u, faults: dict):
    """(probabilities [S, experts], chosen experts [S, k], their gates [S, k])
    for tokens u [S, H]: renormalised over the chosen, then scaled. The choice
    carries no gradient; the gates do."""
    probs = jax.nn.softmax(_einsum(precision, "sh,he->se", u, lp["router"]), axis=-1)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(probs), s["per_token"])
    gates = jnp.take_along_axis(probs, chosen, axis=-1)
    if faults.get("raw_gates"):
        return probs, chosen, gates
    return probs, chosen, s["gate_scale"] * gates / jnp.sum(gates, axis=-1, keepdims=True)


def shared_expert(precision: str, lp: dict, f):
    """The shared expert's result for every token of f [B, T, H]."""
    return dense_mlp(precision, {"w1": lp["sg"], "w3": lp["su"], "w2": lp["sd"]}, f)


def expert_ffn(s: dict, precision: str, lp: dict, f, faults: dict):
    """The expert layer's result for normed input f [B, T, H]: the held routed
    experts' part by position blocks, plus the shared expert."""
    def block(_, fb):
        b, t, h = fb.shape
        u = fb.reshape(b * t, h)
        _, chosen, gates = route(s, precision, lp, u, faults)
        return experts(s, precision, lp, u, chosen, gates).reshape(b, t, h)

    routed = _joined(_in_blocks(block, TOKEN_BLOCK, f))
    return routed if faults.get("no_shared") else routed + shared_expert(precision, lp, f)


def layer(s: dict, precision: str, faults: dict, i: int, x, lp: dict):
    """Layer i on a block of rows x [B, T, H]."""
    x = x + attention(s, precision, i, lp, _rms(x, lp["ln1"], s["eps"]), faults)
    f = _rms(x, lp["ln2"], s["eps"])
    if s["sparse"][i]:
        return x + expert_ffn(s, precision, lp, f, faults)
    return x + dense_mlp(precision, lp, f)


def loss_sum(s: dict, precision: str, faults: dict, p: dict, _bias: dict, block: dict):
    """The cross-entropy SUMMED over the block's labelled positions."""
    x = p["embed"][block["input_ids"]]
    for i in range(s["layers"]):
        x = jax.checkpoint(functools.partial(layer, s, precision, faults, i))(
            x, layer_params(p, i))
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
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = make_step(functools.partial(loss_sum, s, precision, faults), opt, rows_per_block,
                     keep_grads)
    losses, gnorms, first_grads = [], [], None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            rows = len(batch["input_ids"]) // 2 if half else None
            batch = {k: jnp.asarray(batch[k][:rows]) for k in ("input_ids", "labels")}
            p, m, v, loss, gnorm, grads = step(p, m, v, jnp.asarray(i + 1, jnp.int32), {},
                                               batch)
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
