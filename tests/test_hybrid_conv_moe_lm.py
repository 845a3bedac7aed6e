"""A hybrid decoder (gated short-convolution layers among full-attention
layers, a dense lead layer, one chip's share of sigmoid-routed experts chosen
with a constant selection bias, embedding and head tied) trains through
`Trainer`: the program against the benchmark's plain float32 reference
(`perfbench/reference/hybrid_conv_moe_lm.py`) at tiny widths on seeded random
weights, the convolution's causality and tap order, the selection bias
(steers the choice, never the gates; no gradient; constant through scanned
dispatches and a checkpoint), the share test, exact routing under any
imbalance, the tied leaf, flash against einsum, what a rematerialised block
keeps of the flash kernel (one launch a layer and step, the same gradients),
and the per-layer kinds."""

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

from _remat_probe import assert_bit_equal, keep_nothing, pallas_eqns  # noqa: E402
from perfbench.programs import hybrid_conv_moe_lm as adapter  # noqa: E402
from perfbench.reference import hybrid_conv_moe_lm as ref  # noqa: E402
from synapseml_tpu.core import observability as obs  # noqa: E402
from synapseml_tpu.models.flax_nets.llama import (LlamaLM, hybrid_conv_moe_lm,  # noqa: E402
                                                  next_token_labels)
from synapseml_tpu.models.flax_nets.transformer import (Block, Encoder, MoEBlock,  # noqa: E402
                                                        TransformerConfig)
from synapseml_tpu.models.trainer import Trainer, TrainerConfig  # noqa: E402
from synapseml_tpu.ops import attention, sparse_attention  # noqa: E402
from synapseml_tpu.ops.short_conv import gated_short_conv  # noqa: E402

VOCAB = 64
OPT = {"learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "grad_clip": 1.0}
KINDS = ["conv", "full_attention", "conv", "conv"]


def tiny_config(share="0 of 4", **over):
    """The cell's configuration file at widths the CPU holds: a dense conv
    lead, an attention layer and two conv layers; 16 experts, 4 held (3 a
    token); 4 query heads over 2 key heads of 8."""
    with open(os.path.join(ROOT, "perfbench", "configs", "lfm2_24b_a2b_ep8.json")) as f:
        c = json.load(f)
    c.update(hidden_size=32, num_hidden_layers=4, layer_types=list(KINDS), num_dense_layers=1,
             num_attention_heads=4, num_key_value_heads=2, head_dim=8, intermediate_size=48,
             moe_intermediate_size=24, num_experts=4, published_num_experts=16,
             num_experts_per_tok=3, vocab_size=VOCAB, rope_table_len=64, flash_block=8,
             expert_share=share)
    c.update(over)
    return c


def float32_module(config, **over):
    module = adapter.build(config)
    return module.clone(cfg=dataclasses.replace(module.cfg, dtype=jnp.float32, **over))


def rows(seed, n, t):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (n, t), dtype=np.int32)
    return {"input_ids": ids, "labels": next_token_labels(ids)}


def one_chip_mesh():
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    return create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def seeded(config, seed):
    sizes = ref.sizes(config)
    return (sizes, adapter.to_program(ref.init_params(sizes, seed), config),
            adapter.constants_to_program(ref.select_bias(sizes, seed), config))


# ---- the program against the reference -------------------------------------

@pytest.mark.parametrize("t,share,over", [
    (16, "0 of 4", {}), (24, "2 of 4", {}), (16, "0 of 4", {"num_experts_per_tok": 2}),
    (16, "0 of 4", {"layer_types": ["conv", "conv", "full_attention", "full_attention"]})],
    ids=["share_0", "another_share", "top_2", "another_pattern"])
def test_loss_and_every_gradient_leaf_match_the_reference(t, share, over):
    config = tiny_config(share, **over)
    seed = 3
    sizes, params, constants = seeded(config, seed)
    batch = rows(seed, 4, t)
    want = ref.run_steps(sizes, OPT, seed, [batch], rows_per_block=2, keep_grads=True)
    trainer = Trainer(float32_module(config), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))

    def loss_of(p):
        loss, (_, new_vars) = trainer.default_loss(
            {"params": p, "constants": constants},
            {k: jnp.asarray(v) for k, v in batch.items()}, train=True)
        return loss, new_vars["step_stats"]

    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(params)
    assert float(loss) == pytest.approx(want["loss"][0], rel=2e-6)
    got = adapter.from_program(grads, config)
    assert sorted(got) == sorted(want["grads"])
    for name, b in want["grads"].items():
        assert float(jnp.abs(got[name] - b).max()) <= 2e-5 * float(jnp.abs(b).max()) + 1e-9, name
    assert float(stats["moe_held_pairs"]) > 0
    assert 0.0 <= float(stats["moe_bias_steered_share"]) <= 1.0
    assert "moe_aux_loss" not in stats


def test_one_trainer_step_through_the_scanned_path_follows_the_reference():
    config = tiny_config()
    seed = 7
    sizes, params, constants = seeded(config, seed)
    batches = [rows(seed + i, 2, 16) for i in range(4)]
    want = ref.run_steps(sizes, OPT, seed, batches, rows_per_block=1)
    trainer = Trainer(float32_module(config), one_chip_mesh(), TrainerConfig(
        learning_rate=OPT["learning_rate"], weight_decay=OPT["weight_decay"],
        grad_clip=OPT["grad_clip"], **adapter.trainer_options(config)))
    start = jax.tree.map(np.array, constants)      # the step donates its state
    state = trainer.resume_state(params, constants=constants)
    losses = []
    for i in (0, 2):        # two scanned dispatches of two steps
        stacked = {k: np.stack([b[k] for b in batches[i:i + 2]]) for k in batches[0]}
        state, metrics = trainer.train_steps_scan(state, stacked)
        losses += [float(x) for x in np.asarray(metrics["loss"])]
    assert losses == pytest.approx(want["loss"], rel=1e-5)
    assert int(state.step) == 4
    # the selection bias comes out of the scanned steps bit for bit as it went in
    for a, b in zip(jax.tree.leaves(state.constants), jax.tree.leaves(start)):
        assert np.array_equal(np.asarray(a), b)
    from perfbench.lib.norms import leaf_norms

    change = leaf_norms(jax.tree.map(jnp.subtract, adapter.from_program(state.params, config),
                                     ref.init_params(sizes, seed)))
    for name, norm in want["change_norm"].items():
        assert float(change[name]) == pytest.approx(norm, rel=2e-3), name


# ---- the convolution ----------------------------------------------------------

def test_the_convolution_is_causal_and_its_taps_are_in_the_stated_order():
    rng = np.random.default_rng(0)
    b, c, u = (rng.normal(size=(2, 12, 5)).astype(np.float32) for _ in range(3))
    w = rng.normal(size=(5, 3)).astype(np.float32)
    got = np.asarray(gated_short_conv(b, c, u, w))
    a = b * u
    want = np.zeros_like(a)
    for t in range(12):                       # the explicit loop of the equations
        for j in range(3):
            s = t - 2 + j
            if s >= 0:
                want[:, t] += w[:, j] * a[:, s]
    np.testing.assert_allclose(got, c * want, rtol=1e-5, atol=1e-6)
    # tap 2 multiplies the current position: with it alone, no mixing along time
    only_now = np.asarray(gated_short_conv(b, c, u, w * np.array([0, 0, 1], np.float32)))
    np.testing.assert_allclose(only_now, c * a * w[:, 2], rtol=1e-5, atol=1e-6)
    # changing position t moves no output before t
    b2 = b.copy()
    b2[:, 7] += 1.0
    moved = np.abs(np.asarray(gated_short_conv(b2, c, u, w)) - got).max(axis=(0, 2))
    assert (moved[:7] == 0).all() and moved[7] > 0 and moved[9] > 0 and (moved[10:] == 0).all()
    # and the reference's own convolution says the same
    s = {"taps": 3}
    lp = {"w_in": jnp.eye(5, 15), "conv": jnp.asarray(w), "w_out": jnp.eye(5)}
    h = jnp.asarray(rng.normal(size=(1, 12, 5)).astype(np.float32))
    bb = np.asarray(h)      # w_in = [I 0 0]: b = h, c = u = 0
    assert np.abs(np.asarray(ref.short_conv(s, "float32", lp, h))).max() == 0 and bb.any()


def test_a_conv_layer_of_the_model_is_causal_end_to_end():
    config = tiny_config(layer_types=["conv"] * 4)
    _, params, constants = seeded(config, 2)
    module = float32_module(config)
    ids = rows(2, 1, 16)["input_ids"]
    base = module.apply({"params": params, "constants": constants}, ids)
    ids2 = ids.copy()
    ids2[0, 9] = (ids2[0, 9] + 1) % VOCAB
    moved = np.abs(np.asarray(module.apply({"params": params, "constants": constants}, ids2)
                              - base)).max(axis=(0, 2))
    assert (moved[:9] == 0).all() and moved[9] > 0


# ---- the selection bias ---------------------------------------------------------

def moe_cfg(**kw):
    base = dict(hidden=16, n_layers=1, n_heads=2, mlp_dim=8, moe_mlp_dim=12, gated_mlp=True,
                act="silu", moe_experts=8, moe_total_experts=8, moe_top_k=2,
                moe_dispatch="grouped", moe_bias=False, moe_router="sigmoid",
                dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def test_the_bias_changes_the_selection_never_the_gates_and_gets_no_gradient():
    cfg = moe_cfg()
    block = MoEBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16))
    variables = block.init(jax.random.PRNGKey(1), x)
    params = jax.tree.map(lambda v: getattr(v, "value", v), variables["params"],
                          is_leaf=lambda v: hasattr(v, "names"))
    assert variables["constants"]["select_bias"].shape == (8,)
    lp = {"router": params["router"]["kernel"], "wg": params["w_gate"], "wu": params["w_up"],
          "wd": params["w_dn"]}
    s = {"experts": 8, "per_token": 2, "gate_scale": 1.0, "first_expert": 0}
    u = x.reshape(12, 16)

    def run(bias):
        y, sown = block.apply({"params": params, "constants": {"select_bias": bias}}, x,
                              mutable=["intermediates"])
        with jax.default_matmul_precision("highest"):
            scores, chosen, gates = ref.route(s, "float32", lp, bias, u, {})
            want = ref.experts(s, "float32", lp, u, chosen, gates)
        np.testing.assert_allclose(np.asarray(y.reshape(12, 16)), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        return scores, chosen, gates, float(sown["intermediates"]["moe_bias_steered_share"][0])

    _, i0, _, steered0 = run(jnp.zeros(8))
    steer = jnp.zeros(8).at[5].set(10.0)            # expert 5 into every token's choice
    scores, i1, g1, steered1 = run(steer)
    assert (np.asarray(i1) == 5).any(axis=1).all() and not (np.asarray(i0) == 5).any(axis=1).all()
    # gates: the unbiased scores of the chosen, over their sum + 1e-6
    picked = np.take_along_axis(np.asarray(scores), np.asarray(i1), axis=1)
    np.testing.assert_allclose(np.asarray(g1), picked / (picked.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    assert steered0 == 0.0 and 0.0 < steered1 <= 0.5

    def out_sum(bias, p):
        return jnp.sum(block.apply({"params": p, "constants": {"select_bias": bias}}, x) ** 2)

    g_bias, g_params = jax.grad(out_sum, argnums=(0, 1))(steer * 0.01, params)
    assert float(jnp.abs(g_bias).max()) == 0.0
    assert float(jnp.abs(g_params["router"]["kernel"]).max()) > 0.0


def test_the_bias_survives_a_fit_and_a_checkpoint_round_trip_bit_for_bit(tmp_path):
    from synapseml_tpu.parallel.checkpoint import restore_checkpoint, save_checkpoint

    config = tiny_config()
    _, params, constants = seeded(config, 11)
    trainer = Trainer(float32_module(config), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))
    start = jax.tree.map(np.array, constants)      # the step donates its state
    state = trainer.resume_state(params, constants=constants)
    batches = [rows(20 + i, 2, 16) for i in range(4)]
    state = trainer.fit(state, iter(batches), max_steps=4, scan_chunk=2)
    assert int(state.step) == 4
    save_checkpoint(str(tmp_path), state.as_dict(), step=4)
    tree = restore_checkpoint(str(tmp_path), 4)
    again = trainer.resume_state(tree["params"], tree.get("opt_state"), step=4,
                                 constants=tree.get("constants"))
    for tree_ in (state.constants, again.constants):
        flat = jax.tree.leaves(tree_)
        assert len(flat) == 3
        for a, b in zip(flat, jax.tree.leaves(start)):
            assert np.asarray(a).dtype == np.float32
            assert np.array_equal(np.asarray(a), b)
    # a fresh state takes the module's own constants (zeros) from init
    fresh = trainer.init_state({k: v for k, v in batches[0].items()})
    assert all(float(jnp.abs(v).max()) == 0.0 for v in jax.tree.leaves(fresh.constants))
    snap = obs.get_registry().snapshot()
    assert "synapseml_moe_bias_steered_share" in snap
    assert snap["synapseml_moe_held_pairs_total"] > 0


# ---- the share of the experts ------------------------------------------------------

def test_the_shares_parts_add_up_to_the_uncut_layer():
    config = tiny_config()
    sizes = ref.sizes(config)
    whole = dict(sizes, held=sizes["experts"], first_expert=0)
    lp = ref.layer_params(ref.init_params(whole, 9), 1)
    beta = ref.select_bias(whole, 9)["layer1.beta"]
    u = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    with jax.default_matmul_precision("highest"):
        _, chosen, gates = ref.route(whole, "float32", lp, beta, u, {})
        uncut = ref.experts(whole, "float32", lp, u, chosen, gates)
    cfg = float32_module(config).cfg
    total = jnp.zeros_like(uncut)
    for share in range(4):
        c = dataclasses.replace(cfg, moe_first_expert=4 * share)
        part_params = {"router": {"kernel": lp["router"]},
                       "w_gate": lp["wg"][4 * share:4 * share + 4],
                       "w_up": lp["wu"][4 * share:4 * share + 4],
                       "w_dn": lp["wd"][4 * share:4 * share + 4]}
        total = total + MoEBlock(c).apply(
            {"params": part_params, "constants": {"select_bias": beta}}, u[None])[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=2e-5, atol=2e-6)


def test_a_router_skewed_onto_one_held_expert_loses_no_pair():
    cfg = moe_cfg(moe_experts=2, moe_total_experts=8, moe_first_expert=2, moe_top_k=3)
    block = MoEBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 50, 16))
    variables = block.init(jax.random.PRNGKey(1), x)
    params = jax.tree.map(lambda v: getattr(v, "value", v), variables["params"],
                          is_leaf=lambda v: hasattr(v, "names"))
    bias = jnp.zeros(8).at[3].set(50.0)      # every token chooses held expert 3
    y, sown = block.apply({"params": params, "constants": {"select_bias": bias}}, x,
                          mutable=["intermediates"])
    inter = sown["intermediates"]
    held = float(inter["moe_held_pairs"][0])
    assert held >= 50 and float(inter["moe_expert_load_max_ratio"][0]) > 1.0
    s = {"experts": 8, "per_token": 3, "gate_scale": 1.0, "first_expert": 2}
    lp = {"router": params["router"]["kernel"], "wg": params["w_gate"], "wu": params["w_up"],
          "wd": params["w_dn"]}
    with jax.default_matmul_precision("highest"):
        _, chosen, gates = ref.route(s, "float32", lp, bias, x[0], {})
        want = ref.experts(s, "float32", lp, x[0], chosen, gates)
    assert held == float(jnp.sum((chosen >= 2) & (chosen < 4)))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=2e-5, atol=2e-6)


# ---- the tied head, flash, the kinds ---------------------------------------------------

def test_the_tied_embedding_is_one_leaf_whose_gradient_is_the_sum_of_both_uses():
    config = tiny_config()
    _, params, constants = seeded(config, 13)
    assert "lm_head" not in params and set(params) == {"embed", "decoder"}
    module = float32_module(config)
    batch = rows(13, 2, 16)
    ids, labels = jnp.asarray(batch["input_ids"]), jnp.asarray(batch["labels"])
    from synapseml_tpu.models.trainer import cross_entropy_loss

    def loss(p):
        return cross_entropy_loss(module.apply({"params": p, "constants": constants}, ids),
                                  labels)

    tied = jax.grad(loss)(params)["embed"]["embedding"]
    # the same mathematics with the two uses apart
    untied = float32_module(config, tie_embeddings=False)

    def loss_apart(embedding, head):
        p = dict(params, embed={"embedding": embedding}, lm_head={"kernel": head.T})
        return cross_entropy_loss(untied.apply({"params": p, "constants": constants}, ids),
                                  labels)

    e = params["embed"]["embedding"]
    g_look_up, g_head = jax.grad(loss_apart, argnums=(0, 1))(e, e)
    assert float(jnp.abs(g_look_up).max()) > 0 and float(jnp.abs(g_head).max()) > 0
    np.testing.assert_allclose(np.asarray(tied), np.asarray(g_look_up + g_head),
                               rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_flash_equals_einsum_at_head_dim_64_gqa_4_to_1(remat):
    base = dict(hidden=128, n_layers=1, n_heads=8, n_kv_heads=2, head_dim=64, mlp_dim=64,
                norm="rmsnorm", causal=True, use_rope=True, qk_norm=True, attn_bias=False,
                max_len=64, dtype=jnp.float32, remat=remat, flash_block=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 128))
    flash = Encoder(TransformerConfig(attn_impl="flash", **base))
    plain = Encoder(TransformerConfig(attn_impl="einsum", **base))
    variables = plain.init(jax.random.PRNGKey(1), x)

    target = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def loss(model, v, x):     # not the sum of squares: the last norm makes that a constant
        return jnp.sum(model.apply(v, x) * target)

    (a, ga), (b, gb) = (jax.value_and_grad(lambda v: loss(m, v, x))(variables)
                        for m in (flash, plain))
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for (path, u), (_, w) in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                                 jax.tree_util.tree_flatten_with_path(gb)[0]):
        assert float(jnp.abs(u - w).max()) <= 1e-4 * float(jnp.abs(w).max()) + 1e-7, \
            jax.tree_util.keystr(path)
    # the kernel's call carries the scope the device-time readers look for
    text = jax.jit(lambda v: flash.apply(v, x)).lower(variables).as_text(debug_info=True)
    assert "attn.flash" in text


# ---- what the block's rematerialisation keeps of the flash kernel ---------------

def tiny_lm_loss(remat=True):
    """(loss of the parameters, parameters) of the tiny LM with the cell's
    attention widths, 4 query heads of 64 over one key head, and two
    full-attention layers, on 2 rows of 24 tokens (3 blocks of 8)."""
    config = tiny_config(head_dim=64, num_key_value_heads=1,
                         layer_types=["conv", "full_attention", "conv", "full_attention"])
    trainer = Trainer(float32_module(config, remat=remat), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))
    _, params, constants = seeded(config, 3)
    batch = {k: jnp.asarray(v) for k, v in rows(3, 2, 24).items()}
    return (lambda p: trainer.default_loss({"params": p, "constants": constants}, batch,
                                           train=True)[0]), params


@pytest.mark.parametrize("kept,launches", [("output_and_lse", 1), ("nothing", 2)])
def test_a_step_launches_the_flash_kernel_once_a_full_attention_layer(
        kept, launches, monkeypatch):
    if kept == "nothing":
        keep_nothing(monkeypatch)
    loss_of, params = tiny_lm_loss(remat=True)
    assert len(pallas_eqns(jax.make_jaxpr(jax.grad(loss_of))(params).jaxpr)) == 2 * launches


@pytest.mark.parametrize("other", ["no_remat", "remat_that_keeps_nothing"])
def test_gradients_do_not_depend_on_what_the_remat_keeps(other, monkeypatch):
    loss_of, params = tiny_lm_loss(remat=True)
    got = jax.grad(loss_of)(params)     # op by op: no compiler chooses fusions between the two
    if other == "remat_that_keeps_nothing":
        keep_nothing(monkeypatch)
    assert_bit_equal(got, jax.grad(tiny_lm_loss(remat=other != "no_remat")[0])(params))


@pytest.mark.parametrize("over,names", [
    ({"attn_impl": "einsum"}, None),
    ({"attn_impl": "flash"}, attention.REMAT_SAVED_NAMES),
    ({}, None),                 # 'auto': a differentiated call runs the einsum core
    ({"attn_topk": 8, "attn_impl": "einsum"}, sparse_attention.REMAT_SAVED_NAMES),
    ({"attn_topk": 8, "attn_impl": "flash"},
     sparse_attention.REMAT_SAVED_NAMES + attention.REMAT_SAVED_NAMES)],
    ids=["einsum", "flash", "auto", "indexed", "indexed_and_flash"])
def test_the_remat_keeps_the_names_of_the_ops_the_configuration_runs(over, names, monkeypatch,
                                                                      capsys):
    cfg = TransformerConfig(hidden=32, n_layers=1, n_heads=4, mlp_dim=64, causal=True, **over)
    assert Encoder(cfg)._block_cls() is Block                     # remat off: the class itself
    seen = {}
    monkeypatch.setattr(nn, "remat", lambda cls, **kw: seen.update(kw, cls=cls))
    Encoder(dataclasses.replace(cfg, remat=True))._block_cls()
    assert seen["cls"] is Block and seen["static_argnums"] == ()
    if names is None:
        assert seen["policy"] is None
        return
    every = sparse_attention.REMAT_SAVED_NAMES + attention.REMAT_SAVED_NAMES + ("another",)
    kept = []
    for name in every:
        # a kept value is a residual beside the argument; one that is not is computed again
        jax.ad_checkpoint.print_saved_residuals(jax.checkpoint(
            lambda x: jnp.sin(jax.ad_checkpoint.checkpoint_name(jnp.sin(x), name)),
            policy=seen["policy"]), jnp.ones(3))
        kept += [name] * (len(capsys.readouterr().out.splitlines()) - 1)
    assert kept == list(names)


def test_layer_types_and_num_dense_layers_build_the_modules_they_name():
    config = tiny_config()
    _, params, constants = seeded(config, 1)
    dec = params["decoder"]
    for i, kind in enumerate(KINDS):
        layer = dec[f"layer_{i}"]
        assert ("conv" in layer) == (kind == "conv") and ("attn" in layer) == (kind != "conv")
        assert ("router" in layer["mlp"]) == (i >= 1)
    assert dec["layer_0"]["mlp"]["gate"]["kernel"].shape == (32, 48)        # the dense width
    assert dec["layer_1"]["mlp"]["w_gate"].shape == (4, 32, 24)            # the experts' width
    assert not any("bias" in jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    assert sorted(constants["decoder"]) == ["layer_1", "layer_2", "layer_3"]
    # what `init` builds is the same tree
    module = float32_module(config)
    made = module.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    shape = lambda t: jax.tree.map(  # noqa: E731
        lambda v: np.shape(getattr(v, "value", v)), t, is_leaf=lambda v: hasattr(v, "names"))
    assert shape(made["params"]) == shape(params)
    assert shape(made["constants"]) == shape(constants)
    # the published pattern and sizes are the builder's defaults
    cfg = hybrid_conv_moe_lm()
    assert cfg.n_layers == 40 and cfg.layer_types.count("full_attention") == 10
    assert [i for i, k in enumerate(cfg.layer_types) if k != "conv"] == list(range(2, 40, 4))
    assert (cfg.hidden, cfg.mlp_dim, cfg.moe_mlp_dim, cfg.moe_experts, cfg.moe_top_k,
            cfg.moe_dense_layers, cfg.head_dim, cfg.kv_heads) \
        == (2048, 11776, 1536, 64, 4, 2, 64, 8)
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(n_layers=3, layer_types=("conv",))
    with pytest.raises(ValueError, match="grouped"):
        MoEBlock(moe_cfg(moe_dispatch="einsum")).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))
    with pytest.raises(ValueError, match="decode"):
        LlamaLM(dataclasses.replace(module.cfg, attn_impl="einsum"), decode=True).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


def test_existing_configurations_keep_their_parameter_trees():
    """The new fields' defaults leave a plain stack as it was: one mixer, one
    MLP kind, biases where they were, an untied head."""
    from synapseml_tpu.models.flax_nets.llama import llama_tiny

    v = LlamaLM(llama_tiny()).init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    assert set(v) == {"params"} and "lm_head" in v["params"]
    layer = v["params"]["decoder"]["layer_0"]
    assert set(layer) == {"RMSNorm_0", "RMSNorm_1", "attn", "mlp"}
    assert set(layer["mlp"]["gate"]) == {"kernel", "bias"}


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 2.5), ("use_expert_bias", False), ("conv_L_cache", 4),
    ("conv_bias", True)])
def test_the_adapter_refuses_what_the_program_holds_as_constants(key, value):
    """The sigmoid router's gate scale of 1 and selection bias, and the short
    convolution's 3 taps without a bias, are constants of the program, not
    options: a configuration file that states otherwise is refused, not run
    as something else."""
    with pytest.raises(ValueError, match="sigmoid router"):
        adapter.build(tiny_config(**{key: value}))
