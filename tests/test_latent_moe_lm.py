"""A latent-attention decoder (multi-head latent attention: one low-rank
key/value latent a position, a rotary key shared by all heads, keys wider
than values; a dense lead layer; one chip's share of sigmoid-routed experts
with scaled gates beside a shared expert; untied head) trains through
`Trainer`: the program against the benchmark's plain float32 reference
(`perfbench/reference/latent_moe_lm.py`) at tiny widths of which no two are
equal (nope 8, rope 4, value 6, latent 16), so that a mixed-up width cannot
pass; the shared rotary key, RoPE on the rotary dims alone, the latent's norm
before the up-projection, flash against einsum at two widths, the value
operand's own padding, what a rematerialised block keeps of the flash kernel
(one launch a layer and step, the same gradients), the share test with the
shared expert counted once,
the gates' scale, the selection bias's constancy, exact routing under any
imbalance."""

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
from perfbench.programs import latent_moe_lm as adapter  # noqa: E402
from perfbench.reference import latent_moe_lm as ref  # noqa: E402
from synapseml_tpu.models.flax_nets.llama import (LlamaLM, hybrid_conv_moe_lm,  # noqa: E402
                                                  latent_moe_lm, next_token_labels)
from synapseml_tpu.models.flax_nets.transformer import (Attention, Block, Encoder,  # noqa: E402
                                                        LatentAttention, MoEBlock,
                                                        TransformerConfig)
from synapseml_tpu.models.trainer import Trainer, TrainerConfig  # noqa: E402
from synapseml_tpu.ops.attention import flash_attention, reference_attention  # noqa: E402

VOCAB = 64
OPT = {"learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "grad_clip": 1.0}
N, R, V, L = 8, 4, 6, 16        # nope, rope, value, latent: no two equal


def tiny_config(share="0 of 4", **over):
    """The cell's configuration file at widths the CPU holds: a dense lead and
    two expert layers; 16 routed experts, 4 held (3 a token); 4 heads."""
    with open(os.path.join(ROOT, "perfbench", "configs", "moonlight_16b_a3b_ep8.json")) as f:
        c = json.load(f)
    c.update(hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
             num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=N,
             qk_rope_head_dim=R, v_head_dim=V, kv_lora_rank=L, intermediate_size=40,
             moe_intermediate_size=24, n_routed_experts=4, published_n_routed_experts=16,
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


def plain(variables):
    return jax.tree.map(lambda v: getattr(v, "value", v), variables,
                        is_leaf=lambda v: hasattr(v, "names"))


# ---- the program against the reference -------------------------------------

@pytest.mark.parametrize("t,share,over", [
    (16, "0 of 4", {}), (24, "2 of 4", {}), (16, "0 of 4", {"num_experts_per_tok": 2}),
    (16, "0 of 4", {"num_hidden_layers": 4, "first_k_dense_replace": 2})],
    ids=["share_0", "another_share", "top_2", "two_dense_leads"])
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


def test_scanned_dispatches_follow_the_reference_and_leave_the_bias_bit_for_bit():
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
    flat = jax.tree.leaves(state.constants)
    assert len(flat) == 2
    for a, b in zip(flat, jax.tree.leaves(start)):
        assert np.asarray(a).dtype == np.float32 and np.array_equal(np.asarray(a), b)
    from perfbench.lib.norms import leaf_norms

    change = leaf_norms(jax.tree.map(jnp.subtract, adapter.from_program(state.params, config),
                                     ref.init_params(sizes, seed)))
    for name, norm in want["change_norm"].items():
        assert float(change[name]) == pytest.approx(norm, rel=2e-3), name


def test_the_trainer_fits_through_its_chunked_scan():
    config = tiny_config()
    _, params, constants = seeded(config, 11)
    trainer = Trainer(float32_module(config), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))
    state = trainer.resume_state(params, constants=constants)
    state = trainer.fit(state, iter([rows(20 + i, 2, 16) for i in range(4)]),
                        max_steps=4, scan_chunk=2)
    assert int(state.step) == 4
    assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(state.params))


# ---- the latent mixer ---------------------------------------------------------

def attn_cfg(**kw):
    base = dict(hidden=32, n_layers=1, n_heads=4, head_dim=N + R, rope_dim=R, v_head_dim=V,
                kv_latent_rank=L, mlp_dim=16, norm="rmsnorm", causal=True, use_rope=True,
                attn_bias=False, max_len=64, dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


class SpyAttention(LatentAttention):
    """The latent mixer, keeping what it hands to the attention core."""

    def _attend(self, q, k, v, mask):
        self.sow("intermediates", "qkv", (q, k, v))
        return super()._attend(q, k, v, mask)


def mixer(params, x, positions=None, cfg=None):
    out, sown = SpyAttention(cfg or attn_cfg()).apply(
        {"params": params}, x, None, positions, mutable=["intermediates"])
    return (out, *sown["intermediates"]["qkv"][0])


@pytest.fixture(scope="module")
def mixer_params():
    x = jnp.zeros((1, 8, 32))
    return plain(LatentAttention(attn_cfg()).init(jax.random.PRNGKey(5), x)["params"])


def test_the_mixers_tree_has_one_down_projection_and_no_two_equal_widths(mixer_params):
    shapes = jax.tree.map(np.shape, mixer_params)
    assert shapes == {"q": {"kernel": (32, 4, N + R)}, "kv_a": {"kernel": (32, L + R)},
                      "kv_norm": {"scale": (L,)}, "kv_b": {"kernel": (L, 4, N + V)},
                      "o": {"kernel": (4, V, 32)}}


def test_the_rotary_key_is_one_a_position_shared_by_every_head(mixer_params):
    """Hidden dim 0 is read by the down-projection's rotary columns alone, so a
    bump of x[s, 0] moves `k_r` at position s and nothing else: every head's
    key at s moves, by the same vector, in its rotary dims only; no query, no
    value and no other position's key moves; so every head's score against s
    moves and no other."""
    p = jax.tree.map(jnp.array, mixer_params)
    p["q"]["kernel"] = p["q"]["kernel"].at[0].set(0.0)
    p["kv_a"]["kernel"] = p["kv_a"]["kernel"].at[0, :L].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    s = 5
    _, q0, k0, v0 = mixer(p, x)
    _, q1, k1, v1 = mixer(p, x.at[:, s, 0].add(1.0))
    assert k0.shape == (2, 12, 4, N + R) and v0.shape == (2, 12, 4, V)
    # one rotary key a position: the heads' rotary dims are the same vector
    assert all(np.array_equal(np.asarray(k0[:, :, 0, N:]), np.asarray(k0[:, :, i, N:]))
               for i in range(1, 4))
    assert not np.array_equal(np.asarray(k0[:, :, 0, :N]), np.asarray(k0[:, :, 1, :N]))
    assert np.array_equal(np.asarray(q0), np.asarray(q1))
    assert np.array_equal(np.asarray(v0), np.asarray(v1))
    dk = np.abs(np.asarray(k1 - k0))
    assert dk[..., :N].max() == 0.0 and np.delete(dk, s, axis=1).max() == 0.0
    assert (dk[:, s, :, N:].max(axis=-1) > 0).all()                 # every head, at s
    dscore = np.abs(np.asarray(jnp.einsum("bqhd,bkhd->bhqk", q1, k1)
                               - jnp.einsum("bqhd,bkhd->bhqk", q0, k0)))
    assert (dscore[..., s].max(axis=-1) > 0).all() and np.delete(dscore, s, axis=-1).max() == 0.0


def test_rope_turns_the_rotary_dims_only(mixer_params):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    base = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    shifted = base + 7
    out0, q0, k0, _ = mixer(mixer_params, x, base)
    out1, q1, k1, _ = mixer(mixer_params, x, shifted)
    # the first N dims of queries and keys do not see the positions; the last R do
    assert np.array_equal(np.asarray(q0[..., :N]), np.asarray(q1[..., :N]))
    assert np.array_equal(np.asarray(k0[..., :N]), np.asarray(k1[..., :N]))
    assert np.abs(np.asarray(q0[..., N:] - q1[..., N:])).max() > 1e-3
    assert np.abs(np.asarray(k0[..., N:] - k1[..., N:])).max() > 1e-3
    # a common shift keeps every score (relative positions): the output stays
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1), rtol=1e-4, atol=1e-6)
    # other positions for the keys than for the queries do not ...
    stretched = base * 2
    assert np.abs(np.asarray(mixer(mixer_params, x, stretched)[0] - out0)).max() > 1e-4
    # ... unless the rotary columns of W_q and W_a are zero: positions reach nothing else
    p = jax.tree.map(jnp.array, mixer_params)
    p["q"]["kernel"] = p["q"]["kernel"].at[:, :, N:].set(0.0)
    p["kv_a"]["kernel"] = p["kv_a"]["kernel"].at[:, L:].set(0.0)
    np.testing.assert_array_equal(np.asarray(mixer(p, x, stretched)[0]),
                                  np.asarray(mixer(p, x, base)[0]))


def test_the_latents_norm_sits_before_the_up_projection(mixer_params):
    """Scaling the down-projection's latent columns scales the latent, which
    its RMSNorm undoes before `kv_b` reads it: keys' first dims and values stay
    (to the norm's eps); the rotary key, which bypasses the norm, does not
    scale with them. The norm's gain then scales what `kv_b` reads."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 10, 32))
    _, _, k0, v0 = mixer(mixer_params, x)
    p = jax.tree.map(jnp.array, mixer_params)
    p["kv_a"]["kernel"] = p["kv_a"]["kernel"].at[:, :L].multiply(3.0)
    _, _, k1, v1 = mixer(p, x)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(np.asarray(k1[..., :N]), np.asarray(k0[..., :N]), rtol=1e-3,
                               atol=1e-6)
    assert np.array_equal(np.asarray(k1[..., N:]), np.asarray(k0[..., N:]))
    p = jax.tree.map(jnp.array, mixer_params)
    p["kv_norm"]["scale"] = p["kv_norm"]["scale"] * 2.0
    _, _, k2, v2 = mixer(p, x)
    np.testing.assert_allclose(np.asarray(v2), 2.0 * np.asarray(v0), rtol=1e-5, atol=1e-7)
    assert np.array_equal(np.asarray(k2[..., N:]), np.asarray(k0[..., N:]))
    # and the reference's own mixer without the norm is another function
    s = ref.sizes(tiny_config())
    lp = ref.layer_params(ref.init_params(s, 1), 0)
    with jax.default_matmul_precision("highest"):
        a = ref.attention(s, "float32", lp, x)
        b = ref.attention(s, "float32", lp, x, {"no_latent_norm": True})
    assert float(jnp.abs(a - b).max()) > 1e-3 * float(jnp.abs(a).max())


def test_the_latent_form_is_chosen_by_the_rank_and_has_no_decode_cache():
    def mixer_of(cfg):       # the leaves of the layer's token mixer
        made = Block(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, cfg.hidden)))
        return set(made["params"]["attn"])

    assert mixer_of(attn_cfg()) == {"q", "kv_a", "kv_norm", "kv_b", "o"}
    assert mixer_of(attn_cfg(kv_latent_rank=0)) == {"q", "k", "v", "o"}
    assert issubclass(LatentAttention, Attention)       # one attention core for both
    with pytest.raises(ValueError, match="decode"):
        LatentAttention(attn_cfg(), decode=True).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))
    # logical axes: a `heads` mesh axis shards W_q, W_b and W_o and leaves W_a whole
    made = LatentAttention(attn_cfg()).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))["params"]
    names = {k: v["kernel"].names for k, v in made.items() if "kernel" in v}
    assert names == {"q": ("embed", "heads", "kv"), "kv_a": ("embed", None),
                     "kv_b": (None, "heads", "kv"), "o": ("heads", "kv", "embed")}
    assert nn.logical_to_mesh_axes(names["kv_a"], rules=[("embed", None), ("heads", "tensor")]) \
        == jax.sharding.PartitionSpec(None, None)


# ---- flash at two widths ----------------------------------------------------------

def _qkv(t, d=N + R, dv=V, heads=3, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = lambda w: (2, t, heads, w)  # noqa: E731
    return (jax.random.normal(keys[0], shape(d), dtype),
            jax.random.normal(keys[1], shape(d), dtype),
            jax.random.normal(keys[2], shape(dv), dtype), jax.random.normal(keys[3], shape(dv)))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("block,t,variant", [(128, 256, "unmasked"), (16, 48, "masked")])
def test_flash_equals_einsum_with_keys_wider_than_values(block, t, variant, remat):
    from synapseml_tpu.core import observability as obs

    q, k, v, target = _qkv(t)
    builds = lambda: obs.get_registry().snapshot().get(  # noqa: E731
        f'synapseml_flash_kernel_builds_total{{variant="{variant}"}}', 0)
    before = builds()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block, block_k=block)

    core = jax.checkpoint(flash) if remat else flash
    got = jax.value_and_grad(lambda *a: jnp.sum(core(*a) * target), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(reference_attention(*a, causal=True) * target),
                              argnums=(0, 1, 2))(q, k, v)
    assert builds() > before
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    assert [g.shape for g in got[1]] == [q.shape, k.shape, v.shape]
    for name, a, b in zip("qkv", got[1], want[1]):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(jnp.abs(b).max()) + 1e-7, name
    # the scale is 1/sqrt of the true QUERY width, whatever the value width
    wide_v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, N + R - V)))
    np.testing.assert_allclose(np.asarray(flash(q, k, wide_v)[..., :V]), np.asarray(flash(q, k, v)),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("block,t", [(512, 1024), (64, 200)], ids=["unmasked", "masked"])
def test_the_kernels_value_operand_is_padded_to_its_own_lanes(block, t):
    """Queries and keys of 192 run 256 lanes; values of 128 stay 128, and so do
    the output and (in the kernel's scratch) the accumulator."""
    q, k, v, _ = _qkv(t, d=192, dv=128, heads=2, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: flash_attention(
        *a, causal=True, block_q=block, block_k=block))(q, k, v)
    eqn = pallas_eqns(jaxpr.jaxpr)[0]
    tp = -(-t // block) * block
    wide = [x.aval.shape for x in eqn.invars if x.aval.shape[-2:] == (tp, 256)]
    narrow = [x.aval.shape for x in eqn.invars if x.aval.shape[-2:] == (tp, 128)]
    assert len(wide) == 2 and len(narrow) == 1            # q and k; v
    assert eqn.outvars[0].aval.shape == (4, tp, 128)
    assert jaxpr.out_avals[0].shape == (2, t, 2, 128)
    with pytest.raises(ValueError, match="width"):
        flash_attention(q, k[..., :128], v)


@pytest.mark.parametrize("d,block,t,mask", [(64, 16, 48, False), (192, 128, 256, False),
                                            (64, 16, 48, True)])
def test_equal_widths_keep_one_padded_width_throughout(d, block, t, mask):
    """With `v` as wide as `q` the kernel is built as it was before the value
    width was split off: queries, keys, values, the output and the accumulator
    all at `D` rounded to lanes, and the numbers are the plain core's. (That
    such a call still loads the compile-cache entries it did is read on the
    chip: the hybrid decoder's cell after a parent run, `cache_misses` 0.)"""
    q, k, v, target = _qkv(t, d=d, dv=d, heads=2)
    m = jnp.arange(t)[None, :] < jnp.array([[t], [t - 5]]) if mask else None
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, kv_mask=m, causal=True, block_q=block, block_k=block)
    eqn = pallas_eqns(jax.make_jaxpr(flash)(q, k, v).jaxpr)[0]
    dp, tp = -(-d // 128) * 128, -(-t // block) * block
    assert [x.aval.shape for x in eqn.invars if x.aval.ndim == 3 and x.aval.shape[1] == tp] \
        == [(4, tp, dp)] * 3
    assert eqn.outvars[0].aval.shape == (4, tp, dp)
    scratch = eqn.params["jaxpr"].invars[-eqn.params["grid_mapping"].num_scratch_operands:]
    assert scratch[-1].aval.shape == (block, dp)            # the accumulator
    got = jax.value_and_grad(lambda *a: jnp.sum(flash(*a) * target), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(
        reference_attention(*a, kv_mask=m, causal=True) * target), argnums=(0, 1, 2))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, a, b in zip("qkv", got[1], want[1]):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(jnp.abs(b).max()) + 1e-7, name


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_latent_stack_through_flash_equals_the_same_through_einsum(remat):
    base = dict(hidden=32, n_layers=2, n_heads=4, head_dim=N + R, rope_dim=R, v_head_dim=V,
                kv_latent_rank=L, mlp_dim=24, norm="rmsnorm", causal=True, use_rope=True,
                attn_bias=False, max_len=64, dtype=jnp.float32, remat=remat, flash_block=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 32))
    flash = Encoder(TransformerConfig(attn_impl="flash", **base))
    plain_ = Encoder(TransformerConfig(attn_impl="einsum", **base))
    variables = plain_.init(jax.random.PRNGKey(1), x)
    target = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    (a, ga), (b, gb) = (jax.value_and_grad(lambda v: jnp.sum(m.apply(v, x) * target))(variables)
                        for m in (flash, plain_))
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for (path, u), (_, w) in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                                 jax.tree_util.tree_flatten_with_path(gb)[0]):
        assert float(jnp.abs(u - w).max()) <= 1e-4 * float(jnp.abs(w).max()) + 1e-7, \
            jax.tree_util.keystr(path)
    # the scopes the device-time readers look for: the mixer's own, and the core's apart
    text = jax.jit(lambda v: flash.apply(v, x)).lower(variables).as_text(debug_info=True)
    assert "attn.latent" in text and "attn.flash" in text
    assert "attn.latent/attn.flash" not in text


# ---- what the block's rematerialisation keeps of the flash kernel ---------------

def tiny_lm_loss(remat=True):
    """(loss of the parameters, parameters) of the tiny LM with the cell's
    attention widths (keys 128 + 64, values 128) in its three layers, on 2
    rows of 16 tokens (2 blocks of 8)."""
    config = tiny_config(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    trainer = Trainer(float32_module(config, remat=remat), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))
    _, params, constants = seeded(config, 3)
    batch = {k: jnp.asarray(v) for k, v in rows(3, 2, 16).items()}
    return (lambda p: trainer.default_loss({"params": p, "constants": constants}, batch,
                                           train=True)[0]), params


@pytest.mark.parametrize("kept,launches", [("output_and_lse", 1), ("nothing", 2)])
def test_a_step_launches_the_flash_kernel_once_a_layer(kept, launches, monkeypatch):
    if kept == "nothing":
        keep_nothing(monkeypatch)
    loss_of, params = tiny_lm_loss(remat=True)
    assert len(pallas_eqns(jax.make_jaxpr(jax.grad(loss_of))(params).jaxpr)) == 3 * launches


@pytest.mark.parametrize("other", ["no_remat", "remat_that_keeps_nothing"])
def test_gradients_do_not_depend_on_what_the_remat_keeps(other, monkeypatch):
    loss_of, params = tiny_lm_loss(remat=True)
    got = jax.grad(loss_of)(params)     # op by op: no compiler chooses fusions between the two
    if other == "remat_that_keeps_nothing":
        keep_nothing(monkeypatch)
    assert_bit_equal(got, jax.grad(tiny_lm_loss(remat=other != "no_remat")[0])(params))


# ---- the experts: share, shared expert, gates, bias ------------------------------------

def moe_cfg(**kw):
    base = dict(hidden=16, n_layers=1, n_heads=2, mlp_dim=8, moe_mlp_dim=12, gated_mlp=True,
                act="silu", mlp_bias=False, moe_experts=8, moe_total_experts=8, moe_top_k=2,
                moe_dispatch="grouped", moe_bias=False, moe_router="sigmoid",
                moe_gate_scale=2.446, moe_gate_eps=1e-20, moe_shared_mlp_dim=20,
                dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def moe_params(cfg, n=12):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n, cfg.hidden))
    return x, plain(MoEBlock(cfg).init(jax.random.PRNGKey(1), x)["params"])


def as_reference(params):
    lp = {"router": params["router"]["kernel"], "wg": params["w_gate"], "wu": params["w_up"],
          "wd": params["w_dn"]}
    if "shared" in params:
        lp.update(sg=params["shared"]["gate"]["kernel"], su=params["shared"]["up"]["kernel"],
                  sd=params["shared"]["down"]["kernel"])
    return lp


def test_the_shares_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    config = tiny_config()
    sizes = ref.sizes(config)
    whole = dict(sizes, held=sizes["experts"], first_expert=0)
    lp = ref.layer_params(ref.init_params(whole, 9), 1)
    beta = ref.select_bias(whole, 9)["layer1.beta"]
    f = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_ffn(whole, "float32", lp, beta, f, {})     # shared expert included
        shared = ref.shared_expert("float32", lp, f)
    assert float(jnp.abs(shared).max()) > 0.1 * float(jnp.abs(uncut).max())
    cfg = float32_module(config).cfg
    shared_params = {"gate": {"kernel": lp["sg"]}, "up": {"kernel": lp["su"]},
                     "down": {"kernel": lp["sd"]}}
    total, n_shares = jnp.zeros_like(uncut), 4
    for share in range(n_shares):
        c = dataclasses.replace(cfg, moe_first_expert=4 * share)
        part = {"router": {"kernel": lp["router"]}, "shared": shared_params,
                "w_gate": lp["wg"][4 * share:4 * share + 4],
                "w_up": lp["wu"][4 * share:4 * share + 4],
                "w_dn": lp["wd"][4 * share:4 * share + 4]}
        total = total + MoEBlock(c).apply(
            {"params": part, "constants": {"select_bias": beta}}, f)
    # every chip computed the shared expert: it counts once
    total = total - (n_shares - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=2e-5, atol=2e-6)


def test_the_shared_expert_is_every_tokens_and_the_trees_own_subtree():
    cfg = moe_cfg(moe_experts=2, moe_first_expert=2)
    x, params = moe_params(cfg)
    assert set(params) == {"router", "w_gate", "w_up", "w_dn", "shared"}
    assert jax.tree.map(np.shape, params["shared"]) == {
        "gate": {"kernel": (16, 20)}, "up": {"kernel": (16, 20)}, "down": {"kernel": (20, 16)}}
    # a bias that keeps every token off the held experts: what is left is the shared expert
    away = jnp.zeros(8).at[2:4].set(-50.0)
    y = MoEBlock(cfg).apply({"params": params, "constants": {"select_bias": away}}, x)
    with jax.default_matmul_precision("highest"):
        want = ref.shared_expert("float32", as_reference(params), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-6)
    assert (np.abs(np.asarray(y)).max(axis=-1) > 0).all()
    with pytest.raises(ValueError, match="grouped"):
        MoEBlock(moe_cfg(moe_dispatch="einsum", moe_router="softmax", moe_bias=True)).init(
            jax.random.PRNGKey(0), x)
    text = jax.jit(lambda p: MoEBlock(cfg).apply(
        {"params": p, "constants": {"select_bias": away}}, x)).lower(params).as_text(
            debug_info=True)
    assert "moe.shared" in text and "moe.experts" in text and "moe.route" in text


class Router(MoEBlock):
    """The block's sigmoid router alone, on given logits."""

    @nn.compact
    def __call__(self, logits):
        return self._sigmoid_route(logits)


def route_of(cfg, params, bias, x):
    """(logits, gates, chosen experts) as the program's router gives them."""
    logits = x.reshape(-1, cfg.hidden) @ params["router"]["kernel"]
    (gates, chosen), _ = Router(cfg).apply({"constants": {"select_bias": bias}}, logits,
                                           mutable=["intermediates"])
    return logits, gates, chosen


def test_gates_are_the_scale_times_the_normalised_scores_and_the_bias_never_reaches_them():
    cfg = moe_cfg()
    x, params = moe_params(cfg)
    logits, g0, i0 = route_of(cfg, params, jnp.zeros(8), x)
    scores = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(scores, np.asarray(i0), axis=1)
    np.testing.assert_allclose(np.asarray(g0), 2.446 * picked / picked.sum(1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0).sum(1), 2.446, rtol=1e-6)
    # a share's held gates are some of them: they sum to at most the scale
    held = (np.asarray(i0) >= 2) & (np.asarray(i0) < 4)
    assert (np.where(held, np.asarray(g0), 0.0).sum(1) <= 2.446 * (1 + 1e-6)).all()
    steer = jnp.zeros(8).at[5].set(10.0)            # expert 5 into every token's choice
    _, g1, i1 = route_of(cfg, params, steer, x)
    assert (np.asarray(i1) == 5).any(axis=1).all() and not (np.asarray(i0) == 5).any(axis=1).all()
    picked = np.take_along_axis(scores, np.asarray(i1), axis=1)       # the UNbiased scores
    np.testing.assert_allclose(np.asarray(g1), 2.446 * picked / picked.sum(1, keepdims=True),
                               rtol=1e-6)
    # and the whole layer follows the reference's route, experts and shared expert
    s = {"experts": 8, "per_token": 2, "gate_scale": 2.446, "first_expert": 0}
    lp = as_reference(params)
    y = MoEBlock(cfg).apply({"params": params, "constants": {"select_bias": steer}}, x)
    with jax.default_matmul_precision("highest"):
        _, chosen, gates = ref.route(s, "float32", lp, steer, x[0], {})
        want = ref.experts(s, "float32", lp, x[0], chosen, gates) \
            + ref.shared_expert("float32", lp, x)[0]
    np.testing.assert_allclose(np.asarray(gates), np.asarray(g1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=2e-5, atol=2e-6)

    def out_sum(bias, p):
        return jnp.sum(MoEBlock(cfg).apply(
            {"params": p, "constants": {"select_bias": bias}}, x) ** 2)

    g_bias, g_params = jax.grad(out_sum, argnums=(0, 1))(steer * 0.01, params)
    assert float(jnp.abs(g_bias).max()) == 0.0
    assert float(jnp.abs(g_params["router"]["kernel"]).max()) > 0.0


def test_the_defaults_gates_are_bit_for_bit_what_they_were():
    """Scale 1 and 1e-6 are the defaults: the hybrid decoder's router gives the
    gates the literal formula gave, and its program holds no product more."""
    cfg = moe_cfg(moe_gate_scale=1.0, moe_gate_eps=1e-6, moe_shared_mlp_dim=0)
    fields = {f.name: f.default for f in dataclasses.fields(TransformerConfig)}
    assert (fields["moe_gate_scale"], fields["moe_gate_eps"], fields["moe_shared_mlp_dim"],
            fields["kv_latent_rank"]) == (1.0, 1e-6, 0, 0)
    lfm2 = hybrid_conv_moe_lm()
    assert (lfm2.moe_gate_scale, lfm2.moe_gate_eps, lfm2.moe_shared_mlp_dim) == (1.0, 1e-6, 0)
    x, params = moe_params(cfg, n=40)
    assert "shared" not in params
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    logits, gates, chosen = route_of(cfg, params, bias, x)
    scores = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    was = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    assert np.array_equal(np.asarray(gates), np.asarray(was))

    def muls(c):
        jaxpr = jax.make_jaxpr(lambda l: Router(c).apply(
            {"constants": {"select_bias": bias}}, l, mutable=["intermediates"])[0])(logits)
        return sum(e.primitive.name == "mul" for e in jaxpr.jaxpr.eqns)

    assert muls(cfg) == 0 and muls(dataclasses.replace(cfg, moe_gate_scale=2.446)) == 1


def test_a_router_skewed_onto_one_held_expert_loses_no_pair():
    cfg = moe_cfg(moe_experts=2, moe_total_experts=8, moe_first_expert=2, moe_top_k=3)
    x, params = moe_params(cfg, n=50)
    bias = jnp.zeros(8).at[3].set(50.0)      # every token chooses held expert 3
    y, sown = MoEBlock(cfg).apply({"params": params, "constants": {"select_bias": bias}}, x,
                                  mutable=["intermediates"])
    inter = sown["intermediates"]
    held = float(inter["moe_held_pairs"][0])
    assert held >= 50 and float(inter["moe_expert_load_max_ratio"][0]) > 1.0
    s = {"experts": 8, "per_token": 3, "gate_scale": 2.446, "first_expert": 2}
    lp = as_reference(params)
    with jax.default_matmul_precision("highest"):
        _, chosen, gates = ref.route(s, "float32", lp, bias, x[0], {})
        want = ref.experts(s, "float32", lp, x[0], chosen, gates) \
            + ref.shared_expert("float32", lp, x)[0]
    assert held == float(jnp.sum((chosen >= 2) & (chosen < 4)))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=2e-5, atol=2e-6)


# ---- the builder and the adapter ---------------------------------------------------------

def test_the_builders_defaults_are_the_published_sizes_and_the_tree_follows_the_file():
    cfg = latent_moe_lm()
    assert (cfg.hidden, cfg.n_layers, cfg.n_heads, cfg.head_dim, cfg.rope_dim, cfg.value_dim,
            cfg.kv_latent_rank, cfg.mlp_dim, cfg.moe_mlp_dim, cfg.moe_shared_mlp_dim,
            cfg.moe_experts, cfg.moe_top_k, cfg.moe_dense_layers, cfg.vocab_size) \
        == (2048, 27, 16, 192, 64, 128, 512, 11264, 1408, 2816, 64, 6, 1, 163840)
    assert (cfg.moe_router, cfg.moe_gate_scale, cfg.moe_gate_eps, cfg.rope_theta, cfg.norm_eps,
            cfg.tie_embeddings, cfg.attn_impl, cfg.flash_block, cfg.max_len) \
        == ("sigmoid", 2.446, 1e-20, 50000.0, 1e-5, False, "flash", 512, 8192)
    config = tiny_config()
    _, params, constants = seeded(config, 1)
    dec = params["decoder"]
    assert set(params) == {"embed", "decoder", "lm_head"}
    assert set(dec["layer_0"]["mlp"]) == {"gate", "up", "down"}
    assert set(dec["layer_1"]["mlp"]) == {"router", "w_gate", "w_up", "w_dn", "shared"}
    assert dec["layer_1"]["mlp"]["shared"]["gate"]["kernel"].shape == (32, 48)
    assert not any("bias" in jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    assert sorted(constants["decoder"]) == ["layer_1", "layer_2"]
    module = float32_module(config)
    made = module.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    shape = lambda t: jax.tree.map(np.shape, plain(t))  # noqa: E731
    assert shape(made["params"]) == shape(params)
    assert shape(made["constants"]) == shape(constants)
    back = adapter.from_program(params, config)
    again = ref.init_params(ref.sizes(config), 1)
    assert sorted(back) == sorted(again)
    assert all(np.array_equal(np.asarray(back[k]), np.asarray(again[k])) for k in again)
    with pytest.raises(ValueError, match="decode"):
        LlamaLM(dataclasses.replace(module.cfg, attn_impl="einsum"), decode=True).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8),
    ("norm_topk_prob", False), ("q_lora_rank", 1536), ("attention_bias", True),
    ("tie_word_embeddings", True)])
def test_the_adapter_refuses_a_form_the_program_does_not_have(key, value):
    with pytest.raises(ValueError, match="latent decoder"):
        adapter.build(tiny_config(**{key: value}))
