"""A decoder with learned sparse attention (indexer, exact top-k) and one chip's
share of the routed experts trains through `Trainer`: the program against the
benchmark's plain float32 reference (`perfbench/reference/sparse_moe_lm.py`) at
tiny widths on seeded random weights, the share test, exact routing under any
imbalance, exact selection, what the blocks' rematerialisation keeps of the
attention, the tile's hand-written backward pass against autodiff, and the
counters a fit leaves behind."""

import collections
import copy
import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _remat_probe import keep_nothing  # noqa: E402
from perfbench.programs import sparse_moe_lm as adapter  # noqa: E402
from perfbench.reference import sparse_moe_lm as ref  # noqa: E402
from synapseml_tpu.core import observability as obs  # noqa: E402
from synapseml_tpu.models.flax_nets.llama import LlamaLM, next_token_labels  # noqa: E402
from synapseml_tpu.models.flax_nets import transformer  # noqa: E402
from synapseml_tpu.models.flax_nets.transformer import MoEBlock, TransformerConfig  # noqa: E402
from synapseml_tpu.models.trainer import Trainer, TrainerConfig, cross_entropy_loss  # noqa: E402
from synapseml_tpu.ops.grouped_ffn import expert_share_ffn  # noqa: E402
from synapseml_tpu.ops.sparse_attention import indexed_attention, topk_mask  # noqa: E402

VOCAB = 64
OPT = {"learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "grad_clip": 1.0}


def tiny_config(topk=8, share="0 of 4", **over):
    """The cell's configuration file at widths the CPU holds: 16 experts, 4
    held (3 a token), 4 query heads over 2 key heads of 16, a 2 x 8 indexer."""
    with open(os.path.join(ROOT, "perfbench", "configs", "keye_vl2_30b_a3b_ep8.json")) as f:
        c = json.load(f)
    c.update(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=24, num_experts=4,
             published_num_experts=16, num_experts_per_tok=3, vocab_size=VOCAB,
             rope_table_len=64, attn_q_tile=8, expert_share=share)
    c["sa_config"] = dict(c["sa_config"], indexer_num_heads=2, indexer_head_dim=8, topk=topk)
    c.update(over)
    return c


def float32_module(config):
    module = adapter.build(config)
    return module.clone(cfg=dataclasses.replace(module.cfg, dtype=jnp.float32))


def rows(seed, n, t):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (n, t), dtype=np.int32)
    return {"input_ids": ids, "labels": next_token_labels(ids)}


def one_chip_mesh():
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    return create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


# ---- the program against the reference -------------------------------------

@pytest.mark.parametrize("t,topk,share,weights", [
    (6, 8, "0 of 4", {}), (8, 8, "0 of 4", {}), (21, 8, "0 of 4", {}), (21, 5, "2 of 4", {}),
    (21, 8, "0 of 4", {"moe_aux_weight": 0.05, "indexer_loss_weight": 0.25})],
    ids=["T_below_topk", "T_at_topk", "T_above_topk", "another_share", "other_loss_weights"])
def test_loss_and_every_gradient_leaf_match_the_reference(t, topk, share, weights):
    # the file's two loss weights reach the reference through `ref.sizes` and
    # the program through the adapter's `trainer_options`
    config = tiny_config(topk, share, **weights)
    sizes, seed = ref.sizes(config), 3
    batch = rows(seed, 4, t)
    want = ref.run_steps(sizes, OPT, seed, [batch], rows_per_block=2, keep_grads=True)
    trainer = Trainer(float32_module(config), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))
    params = adapter.to_program(ref.init_params(sizes, seed), config)

    def loss_of(p):
        loss, (_, new_vars) = trainer.default_loss(
            {"params": p}, {k: jnp.asarray(v) for k, v in batch.items()}, train=True)
        return loss, new_vars["step_stats"]

    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(params)
    assert float(loss) == pytest.approx(want["loss"][0], rel=2e-6)
    got = adapter.from_program(grads, config)
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                 jax.tree_util.tree_flatten_with_path(want["grads"])[0]):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(jnp.abs(b).max()) + 1e-9, \
            jax.tree_util.keystr(path)
    selected = sum(min(i + 1, topk) for i in range(t)) / (t * (t + 1) / 2)
    assert float(stats["sparse_attn_selected_share"]) == pytest.approx(selected, rel=1e-6)
    assert float(stats["sparse_attn_indexer_kl"]) > 0


def test_the_indexer_loss_reaches_the_indexer_alone_and_the_lm_loss_everything_else():
    config = tiny_config()
    sizes = ref.sizes(config)
    module = float32_module(config)
    params = adapter.to_program(ref.init_params(sizes, 5), config)
    batch = rows(5, 2, 21)

    def terms(p):
        logits, inter = module.apply({"params": p}, batch["input_ids"],
                                     mutable=["intermediates"])
        kl = sum(v[0] for k, v in jax.tree_util.tree_flatten_with_path(
            inter, is_leaf=lambda x: isinstance(x, tuple))[0] if "sparse_attn_indexer_kl" in str(k))
        return cross_entropy_loss(logits, jnp.asarray(batch["labels"])), kl

    g_lm = jax.grad(lambda p: terms(p)[0])(params)
    g_kl = jax.grad(lambda p: terms(p)[1])(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(g_lm)[0],
                                 jax.tree_util.tree_flatten_with_path(g_kl)[0]):
        name = jax.tree_util.keystr(path)
        if "indexer_" in name:
            assert float(jnp.abs(a).max()) == 0.0 and float(jnp.abs(b).max()) > 0, name
        else:
            assert float(jnp.abs(b).max()) == 0.0, name


# ---- what the block's rematerialisation keeps ---------------------------------

PAIRS = 2 * 2      # layers x key segments of `tiny_lm_loss`


def tiny_lm_loss(remat=True, dtype=jnp.float32):
    """(loss of the parameters, parameters) of the tiny LM on 4 rows of 40
    tokens: 5 tiles of 8 queries in 2 segments, top-k 8."""
    config = tiny_config()
    module = adapter.build(config)
    module = module.clone(cfg=dataclasses.replace(module.cfg, remat=remat, dtype=dtype))
    trainer = Trainer(module, one_chip_mesh(), TrainerConfig(**adapter.trainer_options(config)))
    batch = {k: jnp.asarray(v) for k, v in rows(3, 4, 40).items()}
    params = adapter.to_program(ref.init_params(ref.sizes(config), 3), config)
    return (lambda p: trainer.default_loss({"params": p}, batch, train=True)[0]), params


def attention_work(jaxpr, scopes="", in_scan=False, found=None):
    """What a jaxpr holds of the indexed attention, nested jaxprs included:
    ``tile_loops`` (scans with a product of scope ``attn.sparse`` beneath
    them), ``products`` (those products), ``searches`` (the 32 counting
    passes of ``topk_mask`` inside a tile loop), ``operands_<dtype>`` (operands
    of that dtype of the attention's and the indexer's products) and, inside the
    tile loops of the forward pass (two products) and of the backward pass
    (more), ``forward_`` / ``backward_row_maxima`` (``reduce_max`` over the key
    axis) and ``_score_exps`` (``exp`` of a [batch, head, query, key] value)."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        here = f"{scopes}/{eqn.source_info.name_stack}"
        name = eqn.primitive.name
        scan = name == "scan"
        if name == "dot_general" and "attn.sparse" in here:
            found["products"] += 1
        if name == "dot_general" and ("attn.sparse" in here or "attn.indexer" in here):
            found.update(f"operands_{v.aval.dtype.name}" for v in eqn.invars)
        if scan and in_scan and eqn.params["length"] == 32 and "attn.select" in here:
            found["searches"] += 1
        if name == "reduce_max" and eqn.invars[0].aval.ndim - 1 in eqn.params["axes"]:
            found["row_maxima"] += 1
        if name == "exp" and eqn.outvars[0].aval.ndim == 4:
            found["score_exps"] += 1
        before = dict(found)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            attention_work(sub, here, in_scan or scan, found)
        if scan and found["products"] > before.get("products", 0):
            found["tile_loops"] += 1
            kind = "forward_" if found["products"] - before.get("products", 0) == 2 \
                else "backward_"
            for key in ("row_maxima", "score_exps"):
                found[kind + key] += found[key] - before.get(key, 0)
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_step_runs_each_tile_loop_forward_and_once_more_in_its_backward_and_searches_once(
        dtype):
    loss_of, params = tiny_lm_loss(remat=True, dtype=dtype)
    found = attention_work(jax.make_jaxpr(jax.grad(loss_of))(params).jaxpr)
    # a loop forward and the loop of its backward pass, which computes each
    # tile's scores again; none in the rematerialised block between them
    assert found["tile_loops"] == 2 * PAIRS
    # forward the score and value products; backward the score product again
    # and two gradient products of each
    assert found["products"] == (2 + 1 + 4) * PAIRS
    assert found["searches"] == PAIRS
    # the backward pass forms the probabilities from the kept row statistics,
    # in one pass over the scores: no row maximum (the forward's two a loop:
    # scores and index scores), one exponential of score shape
    assert found["forward_row_maxima"] == 2 * PAIRS and found["backward_row_maxima"] == 0
    assert found["forward_score_exps"] == found["backward_score_exps"] == PAIRS
    # no product takes a float32 operand where the inputs are bfloat16: the
    # score and head gradients leave their fusions in the keys' dtype
    assert {k for k in found if k.startswith("operands_")} == {f"operands_{dtype.__name__}"}


@pytest.mark.parametrize("other", ["no_remat", "remat_that_keeps_nothing"])
def test_gradients_do_not_depend_on_what_the_remat_keeps(other, monkeypatch):
    loss_of, params = tiny_lm_loss(remat=True)
    got = jax.jit(jax.grad(loss_of))(params)
    if other == "no_remat":
        loss_of, _ = tiny_lm_loss(remat=False)
        rel = 1e-6                     # another program: float32 sums in another order
    else:
        keep_nothing(monkeypatch)
        loss_of, _ = tiny_lm_loss(remat=True)
        # the attention's loops run again in place of reading what was kept
        found = attention_work(jax.make_jaxpr(jax.grad(loss_of))(params).jaxpr)
        assert found["tile_loops"] == 3 * PAIRS
        rel = 0.0                      # the same arithmetic on the same values
    want = jax.jit(jax.grad(loss_of))(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(a - b).max()) <= rel * float(jnp.abs(b).max()), \
            jax.tree_util.keystr(path)


class BareRematEncoder(nn.Module):
    """`Encoder` with ``remat=True`` as it was before its remat kept anything."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, positions=None):
        block = nn.remat(transformer.Block, static_argnums=())
        for i in range(self.cfg.n_layers):
            x = block(self.cfg, name=f"layer_{i}")(x, mask, positions)
        return x if self.cfg.norm_position == "post" else transformer._norm(self.cfg)(x)


@pytest.mark.parametrize("cfg", [
    TransformerConfig(hidden=32, n_layers=2, n_heads=4, mlp_dim=64, max_len=16,
                      norm_position="post", remat=True),
    TransformerConfig(hidden=32, n_layers=2, n_heads=4, n_kv_heads=2, mlp_dim=64, max_len=16,
                      causal=True, use_rope=True, norm="rmsnorm", gated_mlp=True, act="silu",
                      remat=True)], ids=["bert_shaped", "llama_shaped"])
def test_a_module_without_indexed_attention_keeps_its_rematerialised_program(cfg):
    x = jnp.ones((2, 8, cfg.hidden), jnp.float32)
    mask = jnp.ones((2, 1, 1, 8), bool)
    texts = []
    for module in (transformer.Encoder(cfg), BareRematEncoder(cfg)):
        variables = module.init(jax.random.PRNGKey(0), x, mask)
        grad = jax.grad(lambda v, x: jnp.sum(module.apply(v, x, mask) ** 2))
        texts.append((str(jax.make_jaxpr(grad)(variables, x)),
                      jax.jit(grad).lower(variables, x).as_text()))
    assert texts[0][0] == texts[1][0]
    assert texts[0][1] == texts[1][1]


# ---- selection ---------------------------------------------------------------

@pytest.mark.parametrize("t,topk", [(6, 8), (8, 8), (40, 8), (40, 13)])
def test_selected_sets_equal_the_references(t, topk):
    index = jax.random.normal(jax.random.PRNGKey(t + topk), (3, t, t), jnp.float32)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool)), index.shape)
    want = ref.key_sets({"topk": topk}, index, 0)
    got = topk_mask(index, causal, topk)
    assert bool(jnp.all(got == want))
    assert [int(x) for x in jnp.sum(got[0], axis=-1)] == [min(i + 1, topk) for i in range(t)]


def test_equal_scores_are_taken_lowest_index_first():
    index = jnp.asarray([[[1.0, 0.5, 0.5, 0.5, 0.5, 2.0, -0.0, 0.0]]])
    got = topk_mask(index, jnp.ones(index.shape, bool), 3)
    assert got[0, 0].tolist() == [True, True, False, False, False, True, False, False]
    _, picked = jax.lax.top_k(index, 3)
    assert sorted(picked[0, 0].tolist()) == [0, 1, 5]
    zeros = topk_mask(jnp.zeros((1, 1, 6)) * jnp.asarray([-1.0, 1, 1, -1, 1, 1]),
                      jnp.asarray([[[False, True, True, True, True, True]]]), 2)
    assert zeros[0, 0].tolist() == [False, True, True, False, False, False]


def test_no_key_outside_the_selection_has_weight():
    b, t, h, kv, d, hi, di, topk = 2, 24, 4, 2, 8, 2, 4, 5
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    q = jax.random.normal(keys[0], (b, t, h, d))
    k = jax.random.normal(keys[1], (b, t, kv, d))
    v = jax.random.normal(keys[2], (b, t, kv, d))
    qi = jax.random.normal(keys[3], (b, t, hi, di))
    ki = jax.random.normal(keys[4], (b, t, di))
    wi = jax.random.normal(keys[5], (b, t, hi))
    out, _, share = indexed_attention(q, k, v, qi, ki, wi, topk=topk, q_tile=8)
    index = jnp.einsum("btj,bjts->bts", wi, jax.nn.relu(
        jnp.einsum("btjd,bsd->bjts", qi, ki))) / np.sqrt(hi * di)
    chosen = ref.key_sets({"topk": topk}, index, 0)
    # the last query's unselected keys and values may hold anything
    outside = ~chosen[:, -1]
    noise = 1e3 * jax.random.normal(keys[6], v.shape)
    v2 = jnp.where(outside[:, :, None, None], noise, v)
    k2 = jnp.where(outside[:, :, None, None], noise, k)
    out2, _, _ = indexed_attention(q, k2, v2, qi, ki, wi, topk=topk, q_tile=8)
    assert bool(jnp.all(out[:, -1] == out2[:, -1]))
    assert float(share) == pytest.approx(
        sum(min(i + 1, topk) for i in range(t)) / (t * (t + 1) / 2))


# ---- the tile's hand-written backward pass --------------------------------------

def plain_indexed_attention(q, k, v, qi, ki, wi, topk, kv_mask=None):
    """``indexed_attention``'s arithmetic over the whole causal square at
    once, for autodiff to differentiate: no tiles, no segments, nothing kept
    and nothing written by hand. Returns ``(out, kl)``."""
    b, t, h, d = q.shape
    hi, di = qi.shape[2:]
    f32 = jnp.float32

    def masked_softmax(x, mask, log=False):
        x = jnp.where(mask, x, jnp.finfo(f32).min)
        shifted = x - jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
        if log:
            return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
        return jnp.exp(shifted) / jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)

    candidates = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool)), (b, t, t))
    if kv_mask is not None:
        candidates = candidates & kv_mask[:, None, :]
    head = jnp.einsum("btjd,bsd->btjs", qi, ki, preferred_element_type=f32)
    index = jnp.sum(jax.nn.relu(head) * wi.astype(f32)[..., None], axis=2) / np.sqrt(hi * di)
    chosen = topk_mask(index, candidates, topk)
    keys, values = (jnp.repeat(x, h // k.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bthd,bshd->bhts", q, keys, preferred_element_type=f32) / np.sqrt(d)
    probs = masked_softmax(scores, chosen[:, None])
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), values)
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
    live = chosen & (target > 0)
    kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                   - masked_softmax(index, chosen, log=True)), 0.0)
    return out, jnp.sum(kl) / (b * t)


def attention_inputs(t, kv, dtype=jnp.float32, b=2, h=4, d=8, hi=2, di=4):
    keys = jax.random.split(jax.random.PRNGKey(t + kv), 7)
    shapes = [(b, t, h, d), (b, t, kv, d), (b, t, kv, d), (b, t, hi, di), (b, t, di), (b, t, hi)]
    inputs = [jax.random.normal(key, shape).astype(dtype) for key, shape in zip(keys, shapes)]
    return inputs, jax.random.normal(keys[6], (b, t, h, d)).astype(dtype)


def attention_gradients(fn, inputs, d_out, d_kl):
    """Gradients to all six inputs of ``sum(out * d_out) + d_kl * kl``."""
    def scalar(*xs):
        out, kl = fn(*xs)[:2]
        return jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32)) + d_kl * kl

    return jax.jit(jax.grad(scalar, argnums=tuple(range(6))))(*inputs)


@pytest.mark.parametrize("t,topk,kv,padded", [
    (6, 8, 2, 0), (8, 8, 2, 0), (24, 8, 2, 0), (21, 8, 2, 0), (24, 8, 2, 5), (24, 5, 4, 0),
    (40, 8, 2, 0)],
    ids=["T_below_topk", "T_at_topk", "T_above_topk", "T_not_a_multiple_of_the_tile",
         "padded_keys", "one_query_head_a_key_head", "five_tiles_in_two_segments"])
def test_the_hand_written_backward_pass_gives_autodiffs_gradients(t, topk, kv, padded):
    # 4 query heads over `kv` key heads, tiles of 8 queries: every case but
    # the first two has two key segments
    inputs, d_out = attention_inputs(t, kv)
    kv_mask = None if not padded else \
        jnp.arange(t)[None, :] < jnp.asarray([[t], [t - padded]])
    got = attention_gradients(
        lambda *xs: indexed_attention(*xs, topk=topk, q_tile=8, kv_mask=kv_mask),
        inputs, d_out, 0.7)
    want = attention_gradients(
        lambda *xs: plain_indexed_attention(*xs, topk, kv_mask), inputs, d_out, 0.7)
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "wi"), got, want):
        assert float(jnp.abs(b).max()) > 0, name
        assert float(jnp.abs(a - b).max()) <= 2e-6 * float(jnp.abs(b).max()), name


def test_in_bfloat16_the_gradients_stay_within_its_rounding_of_float32s():
    # the products take bfloat16 operands and add in float32: the float32
    # gradients of the same (bfloat16) numbers are the measure
    inputs, d_out = attention_inputs(24, 2, jnp.bfloat16)
    attend = lambda *xs: indexed_attention(*xs, topk=8, q_tile=8)  # noqa: E731
    got = attention_gradients(attend, inputs, d_out, 0.7)
    want = attention_gradients(attend, [x.astype(jnp.float32) for x in inputs],
                               d_out.astype(jnp.float32), 0.7)
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "wi"), got, want):
        assert a.dtype == jnp.bfloat16, name
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) \
            <= 2 ** -6 * float(jnp.abs(b).max()), name


# ---- the experts' share --------------------------------------------------------

def _moe_cfg(held, total, first, k=3, hidden=32, width=24):
    return TransformerConfig(hidden=hidden, mlp_dim=width, n_heads=4, act="silu",
                             gated_mlp=True, moe_experts=held, moe_total_experts=total,
                             moe_first_expert=first, moe_top_k=k, moe_dispatch="grouped",
                             moe_bias=False, dtype=jnp.float32)


def _full_layer(seed, total=16, hidden=32, width=24):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": 0.5 * jax.random.normal(keys[0], (hidden, total)),
            "wg": 0.3 * jax.random.normal(keys[1], (total, hidden, width)),
            "wu": 0.3 * jax.random.normal(keys[2], (total, hidden, width)),
            "wd": 0.3 * jax.random.normal(keys[3], (total, width, hidden))}, \
        jax.random.normal(keys[4], (2, 20, hidden))


def _share_output(lp, u, held, first, total=16):
    cfg = _moe_cfg(held, total, first)
    params = {"router": {"kernel": lp["router"]}, "w_gate": lp["wg"][first:first + held],
              "w_up": lp["wu"][first:first + held], "w_dn": lp["wd"][first:first + held]}
    return MoEBlock(cfg).apply({"params": params}, u, mutable=["intermediates"])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What every chip of the deployment computes, added, is what the uncut
    reference gives for the whole expert layer (16 experts, 8 shares of 2)."""
    lp, u = _full_layer(1)
    s = {"experts": 16, "per_token": 3, "norm_topk": True, "first_expert": 0}
    flat = u.reshape(-1, u.shape[-1])
    _, chosen, gates = ref.route(s, "float32", lp, flat)
    whole = ref.experts(s, "float32", lp, flat, chosen, gates).reshape(u.shape)
    parts, pairs = 0.0, 0.0
    for share in range(8):
        y, inter = _share_output(lp, u, 2, 2 * share)
        parts = parts + y
        pairs += float(inter["intermediates"]["moe_held_pairs"][0])
    assert float(jnp.abs(parts - whole).max()) <= 1e-5 * float(jnp.abs(whole).max())
    assert pairs == flat.shape[0] * 3           # every pair is held by exactly one share


@pytest.mark.parametrize("skew", ["one_expert_takes_most", "every_pair_is_held"])
def test_a_skewed_router_loses_no_pair(skew):
    lp, u = _full_layer(2)
    if skew == "one_expert_takes_most":      # expert 1 is every token's first choice
        lp["router"] = lp["router"].at[:, 1].set(0.0)
        u = u.at[..., 0].set(40.0)
        lp["router"] = lp["router"].at[0, 1].set(1.0)
    else:                                    # all three choices of every token are held
        lp["router"] = lp["router"].at[:, 4:].add(-100.0 * jnp.sign(u.mean()))
        u = jnp.abs(u)
        lp["router"] = jnp.where(jnp.arange(16)[None, :] < 4, jnp.abs(lp["router"]),
                                 -jnp.abs(lp["router"]))
    s = {"experts": 16, "per_token": 3, "norm_topk": True, "first_expert": 0}
    flat = u.reshape(-1, u.shape[-1])
    _, chosen, gates = ref.route(s, "float32", lp, flat)
    held_lp = {k: (v[:4] if k != "router" else v) for k, v in lp.items()}
    want = ref.experts(s, "float32", held_lp, flat, chosen, gates).reshape(u.shape)
    y, inter = _share_output(lp, u, 4, 0)
    held_pairs = int(jnp.sum(chosen < 4))
    if skew == "one_expert_takes_most":
        assert int(jnp.sum(chosen == 1)) == flat.shape[0]
        assert float(inter["intermediates"]["moe_expert_load_max_ratio"][0]) > 2.0
    else:
        assert held_pairs == flat.shape[0] * 3      # the worst case the buffers are sized for
    assert float(inter["intermediates"]["moe_held_pairs"][0]) == held_pairs
    assert float(jnp.abs(y - want).max()) <= 1e-5 * float(jnp.abs(want).max())


def test_grouped_gradients_match_a_dense_computation():
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    s_, h, m, e, total, k = 37, 16, 24, 4, 16, 3
    x = jax.random.normal(keys[0], (s_, h))
    w = [0.3 * jax.random.normal(keys[i], shape) for i, shape in
         ((1, (e, h, m)), (2, (e, h, m)), (3, (e, m, h)))]
    logits = jax.random.normal(keys[4], (s_, total))

    def gate(logits):
        gv, gi = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        return gv / gv.sum(-1, keepdims=True), gi

    def dense(x, logits, wg, wu, wd):
        gv, gi = gate(logits)
        return sum(jnp.sum(jnp.where(gi == 4 + i, gv, 0), -1)[:, None]
                   * ((jax.nn.silu(x @ wg[i]) * (x @ wu[i])) @ wd[i]) for i in range(e))

    def grouped(x, logits, wg, wu, wd):
        gv, gi = gate(logits)
        return expert_share_ffn(x, gv, gi, wg, wu, wd, first_expert=4, block_rows=8)[0]

    grad = lambda fn: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))(x, logits, *w)
    for a, b in zip(grad(grouped), grad(dense)):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())


def test_a_share_needs_the_grouped_layout_and_biases_are_optional():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    with pytest.raises(ValueError, match="grouped"):
        cfg = dataclasses.replace(_moe_cfg(4, 16, 0), moe_dispatch="scatter", moe_bias=True)
        MoEBlock(cfg).init(jax.random.PRNGKey(1), x)
    for dispatch in ("einsum", "scatter"):
        cfg = TransformerConfig(hidden=32, mlp_dim=24, n_heads=4, moe_experts=4, moe_top_k=2,
                                moe_dispatch=dispatch, moe_capacity_factor=2.0,
                                dtype=jnp.float32)
        with_bias = MoEBlock(cfg).init(jax.random.PRNGKey(1), x)["params"]
        bare_cfg = dataclasses.replace(cfg, moe_bias=False)
        made = MoEBlock(bare_cfg).init(jax.random.PRNGKey(1), x)["params"]
        assert set(with_bias) - set(made) == {"b_up", "b_dn"}
        bare = {k: v for k, v in with_bias.items() if k in made}
        y0 = MoEBlock(cfg).apply({"params": with_bias}, x)
        y1 = MoEBlock(bare_cfg).apply({"params": bare}, x)
        assert bool(jnp.all(y0 == y1))      # zero biases: the same result


def test_head_dim_is_a_field_that_defaults_to_hidden_over_heads():
    assert TransformerConfig(hidden=768, n_heads=12).head_dim == 64
    assert TransformerConfig(hidden=2048, n_heads=32, head_dim=128).head_dim == 128
    cfg = TransformerConfig(hidden=32, n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=VOCAB,
                            n_layers=1, mlp_dim=16, causal=True, use_rope=True, max_len=16,
                            dtype=jnp.float32)
    v = LlamaLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    attn = jax.tree.map(jnp.shape, v["params"]["decoder"]["layer_0"]["attn"],
                        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
    assert attn["q"]["kernel"].value == (32, 4, 16) and attn["o"]["kernel"].value == (4, 16, 32)


# ---- the loss ---------------------------------------------------------------

def test_a_row_mask_covers_every_token_of_its_row_and_negative_labels_are_left_out():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
    labels = jnp.asarray(np.random.default_rng(0).integers(0, 7, (3, 5)))
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), labels[..., None], -1)[..., 0]
    valid = jnp.asarray([1.0, 0.0, 1.0])
    got = cross_entropy_loss(logits, labels, valid)
    assert float(got) == pytest.approx(float((nll[0].sum() + nll[2].sum()) / 10), rel=1e-6)
    last_out = labels.at[:, -1].set(-100)
    got = cross_entropy_loss(logits, last_out, valid)
    assert float(got) == pytest.approx(
        float((nll[0, :4].sum() + nll[2, :4].sum()) / 8), rel=1e-6)
    assert float(cross_entropy_loss(logits, last_out)) == pytest.approx(
        float(nll[:, :4].mean()), rel=1e-6)
    # a label a row, as the classifiers give it, is read as before
    rows_ = cross_entropy_loss(logits[:, 0], labels[:, 0], valid)
    assert float(rows_) == pytest.approx(float((nll[0, 0] + nll[2, 0]) / 2), rel=1e-6)


# ---- through DataLoader and Trainer.fit ---------------------------------------

@pytest.fixture(scope="module")
def fitted():
    """The tiny LM, two dispatches of 4 steps through `DataLoader` and
    `Trainer.fit`'s chunked scan, from given weights."""
    from synapseml_tpu.data import DataLoader
    from synapseml_tpu.data.source import MemorySource

    obs.reset_tracer()
    obs.reset_registry()
    config = tiny_config()
    module = adapter.build(config)             # bfloat16 compute, as the cell
    trainer = Trainer(module, one_chip_mesh(), TrainerConfig(learning_rate=1e-3))
    sizes = ref.sizes(config)
    state = trainer.resume_state(adapter.to_program(ref.init_params(sizes, 9), config))
    loader = DataLoader(MemorySource(rows(9, 32, 21)), 4, seed=9, epochs=None,
                        drop_remainder=True, shuffle_rows="full")
    try:
        state = trainer.fit(state, iter(loader), max_steps=8, scan_chunk=4, log_every=4)
    finally:
        loader.close()
    return {"state": state, "spans": obs.get_tracer().finished_spans(),
            "snapshot": obs.get_registry().snapshot(),
            "exposition": obs.get_registry().exposition(), "metrics": list(trainer.metrics)}


def test_the_tiny_lm_trains_two_dispatches_through_the_loader(fitted):
    assert int(fitted["state"].step) == 8
    dispatches = [s for s in fitted["spans"] if s.name == "train.dispatch"]
    assert [s.attributes["program"] for s in dispatches] == ["scan", "scan"]
    assert np.isfinite(fitted["metrics"][-1]["loss"])


def test_the_fit_leaves_the_new_counters_and_the_fetch_spans_values(fitted):
    snap = fitted["snapshot"]
    tokens, layers = 4 * 21, 2
    # 8 steps x 2 layers x pairs held: near tokens x 3 x 4/16 a layer, never over the worst case
    assert 0 < snap["synapseml_moe_held_pairs_total"] <= 8 * layers * tokens * 3
    assert snap["synapseml_moe_expert_load_max_ratio"] >= 1.0
    assert snap["synapseml_sparse_attn_selected_share"] == pytest.approx(
        sum(min(i + 1, 8) for i in range(21)) / (21 * 22 / 2), rel=1e-5)
    assert snap["synapseml_sparse_attn_indexer_kl"] > 0
    fetches = [s for s in fitted["spans"] if s.name == "train.fetch"]
    assert len(fetches) == 2
    assert sum(s.attributes["moe_held_pairs"] for s in fetches) \
        == snap["synapseml_moe_held_pairs_total"]
    last = fetches[-1].attributes
    for key in ("moe_expert_load_max_ratio", "sparse_attn_selected_share",
                "sparse_attn_indexer_kl"):
        assert last[key] == snap["synapseml_" + key]


def test_metrics_endpoint_shows_the_four_series(fitted):
    for series in ("synapseml_moe_held_pairs_total", "synapseml_moe_expert_load_max_ratio",
                   "synapseml_sparse_attn_selected_share", "synapseml_sparse_attn_indexer_kl"):
        assert f"\n{series} " in fitted["exposition"], series


def test_a_module_without_the_mechanism_keeps_its_two_step_metrics(mesh_dp8):
    from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_tiny

    trainer = Trainer(BertClassifier(bert_tiny(), num_classes=2), mesh_dp8, TrainerConfig())
    batch = {"input_ids": np.zeros((8, 16), np.int32), "attention_mask": np.ones((8, 16), np.int32),
             "labels": np.zeros((8,), np.int32)}
    state = trainer.init_state(batch, jax.random.PRNGKey(0))
    _, metrics = trainer.train_step(state, batch)
    assert set(metrics) == {"loss", "grad_norm"}


def test_next_token_labels():
    ids = np.arange(12, dtype=np.int32).reshape(2, 6)
    labels = next_token_labels(ids)
    assert labels[:, :-1].tolist() == ids[:, 1:].tolist() and labels[:, -1].tolist() == [-100] * 2
    assert copy.copy(labels).dtype == np.int32
