"""A decoder of sliding-window and full attention layers (each kind its own
query heads over shared key/value heads, its own window and rotary: partial
YaRN on the full layers, every dim at another base on the sliding ones; a
sigmoid gate a head; a dense lead; one chip's share of softmax-routed experts
with scaled gates beside a shared expert; untied head) trains through
`Trainer`: the program against the benchmark's plain float32 reference
(`perfbench/reference/window_moe_lm.py`) at tiny widths (5 layers of both
kinds, 6 and 8 heads over 2 key/value heads, window 16, T 64); the windowed
flash op against the einsum core under the band's mask, forward and
gradients, both kernel variants, with ragged lengths and all-masked edge
tiles; the step tables at the cell's shape; what a rematerialised block keeps
of both kernels; the expert share; the rotary against `transformers`' YaRN;
the configuration's refusals.

Tolerances: the module runs in float32 here (the CPU's float32 product is
exact to rounding), so program and reference differ by float32 rounding
alone: the loss to 2e-6 relative, every gradient leaf to 2e-5 of its largest
entry (the latent decoder's, tests/test_latent_moe_lm.py). The flash op
against the einsum core: the loss to 1e-5 relative and every gradient to
1e-4 of its largest entry (the blockwise softmax sums in another order,
tests/test_latent_moe_lm.py's flash tests); a band one key narrower misses by
orders more (`test_a_band_one_key_off_fails_the_tolerance`)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _remat_probe import keep_nothing, pallas_eqns  # noqa: E402
from perfbench.programs import window_moe_lm as adapter  # noqa: E402
from perfbench.reference import window_moe_lm as ref  # noqa: E402
from synapseml_tpu.core import observability as obs  # noqa: E402
from synapseml_tpu.models.flax_nets.llama import (LlamaLM, next_token_labels,  # noqa: E402
                                                  window_moe_lm)
from synapseml_tpu.models.flax_nets.transformer import (Attention, AttnKind,  # noqa: E402
                                                        MoEBlock, TransformerConfig, Yarn,
                                                        rope_frequencies)
from synapseml_tpu.models.trainer import Trainer, TrainerConfig  # noqa: E402
from synapseml_tpu.ops.attention import (_work_steps, flash_attention,  # noqa: E402
                                         reference_attention)

VOCAB = 64
OPT = {"learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "grad_clip": 1.0}
FULL, SLIDING = "full_attention", "sliding_attention"
LOSS_REL, GRAD_REL = 2e-6, 2e-5         # float32 rounding: the module docstring
FLASH_LOSS_REL, FLASH_GRAD_REL = 1e-5, 1e-4


def tiny_config(**over):
    """The cell's configuration file at widths the CPU holds: layers full,
    sliding x 3, full; 6 and 8 query heads over 2 key/value heads of 8 (4
    rotary dims on the full layers); window 16; a dense lead, then 16 routed
    experts of which 4 are held (3 a token) beside a shared expert."""
    with open(os.path.join(ROOT, "perfbench", "configs", "laguna_xs2_ep8.json")) as f:
        c = json.load(f)
    c.update(hidden_size=32, num_attention_heads=6, num_attention_heads_per_layer=[6, 8, 8, 8, 6],
             num_key_value_heads=2, head_dim=8, sliding_window=16, intermediate_size=40,
             moe_intermediate_size=24, shared_expert_intermediate_size=20, num_experts=4,
             published_num_experts=16, num_experts_per_tok=3, vocab_size=VOCAB,
             rope_table_len=64, flash_block=16, expert_share="0 of 4")
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


def trainer_loss(config, **over):
    trainer = Trainer(float32_module(config, **over), one_chip_mesh(),
                      TrainerConfig(**adapter.trainer_options(config)))

    def loss_of(params, batch):
        loss, (_, new_vars) = trainer.default_loss(
            {"params": params}, {k: jnp.asarray(v) for k, v in batch.items()}, train=True)
        return loss, new_vars["step_stats"]

    return trainer, loss_of


# ---- the program against the reference -------------------------------------

@pytest.mark.parametrize("impl,t,share", [("flash", 64, "0 of 4"), ("einsum", 64, "0 of 4"),
                                          ("flash", 56, "3 of 4")],
                         ids=["flash", "einsum", "ragged_T_another_share"])
def test_loss_and_every_gradient_leaf_match_the_reference(impl, t, share):
    config = tiny_config(expert_share=share)
    seed = 3
    sizes = ref.sizes(config)
    params = adapter.to_program(ref.init_params(sizes, seed), config)
    batch = rows(seed, 2, t)
    want = ref.run_steps(sizes, OPT, seed, [batch], rows_per_block=1, keep_grads=True)
    _, loss_of = trainer_loss(config, attn_impl=impl)
    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_of, has_aux=True))(params, batch)
    assert float(loss) == pytest.approx(want["loss"][0], rel=LOSS_REL)
    got = adapter.from_program(grads, config)
    assert sorted(got) == sorted(want["grads"])
    for name, b in want["grads"].items():
        assert float(jnp.abs(got[name] - b).max()) <= GRAD_REL * float(jnp.abs(b).max()) + 1e-9, \
            name
    assert float(stats["moe_held_pairs"]) > 0


@pytest.mark.parametrize("fault", ["no_window", "no_head_gate", "plain_rope", "raw_gates"])
def test_each_mechanism_moves_the_reference_beyond_the_tolerance(fault):
    """What the program is held to is not rounding: leaving out any one of the
    window, the heads' gates, YaRN or the gates' scale moves the loss or a
    gradient leaf far past the tolerances above."""
    config = tiny_config()
    sizes = ref.sizes(config)
    batch = rows(5, 2, 64)
    want = ref.run_steps(sizes, OPT, 5, [batch], keep_grads=True)
    other = ref.run_steps(sizes, OPT, 5, [batch], keep_grads=True, **{fault: True})
    loss_off = abs(other["loss"][0] - want["loss"][0]) / abs(want["loss"][0])
    grad_off = max(float(jnp.abs(other["grads"][n] - b).max() / jnp.abs(b).max())
                   for n, b in want["grads"].items())
    assert loss_off > 100 * LOSS_REL or grad_off > 100 * GRAD_REL, (loss_off, grad_off)


def test_the_trainer_fits_through_its_chunked_scan_as_the_reference_steps():
    config = tiny_config()
    seed = 7
    sizes = ref.sizes(config)
    batches = [rows(seed + i, 2, 32) for i in range(4)]
    want = ref.run_steps(sizes, OPT, seed, batches, rows_per_block=1)
    trainer = Trainer(float32_module(config), one_chip_mesh(), TrainerConfig(
        learning_rate=OPT["learning_rate"], weight_decay=OPT["weight_decay"],
        grad_clip=OPT["grad_clip"], **adapter.trainer_options(config)))
    state = trainer.resume_state(adapter.to_program(ref.init_params(sizes, seed), config))
    losses = []
    for i in (0, 2):        # two scanned dispatches of two steps
        stacked = {k: np.stack([b[k] for b in batches[i:i + 2]]) for k in batches[0]}
        state, metrics = trainer.train_steps_scan(state, stacked)
        losses += [float(x) for x in np.asarray(metrics["loss"])]
    assert losses == pytest.approx(want["loss"], rel=1e-5)
    state = trainer.fit(state, iter(batches), max_steps=4, scan_chunk=2)
    assert int(state.step) == 8
    assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(state.params))


# ---- the windowed flash op --------------------------------------------------------

def _qkv(t, heads=3, d=64, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (2, t, heads, d)) for k in keys)


def _builds(variant):
    return obs.get_registry().snapshot().get(
        f'synapseml_flash_kernel_builds_total{{variant="{variant}"}}', 0)


@pytest.mark.parametrize("t,block,window,mask", [
    (100, 16, 24, False),      # T a multiple of neither; masked variant (blocks under a lane tile)
    (100, 16, 37, True),       # a key mask too
    (384, 128, 200, False),    # the unmasked variant: the band's last tiles masked whole for rows
    (256, 128, 512, False),    # a window longer than the row: the causal square
    (64, 16, 2, False)],       # each query its own key and the one before
    ids=["ragged", "key_mask", "unmasked_variant", "window_over_T", "window_2"])
def test_windowed_flash_equals_einsum_under_the_band(t, block, window, mask):
    q, k, v, target = _qkv(t)
    m = (jnp.arange(t)[None, :] < jnp.array([[t], [t - 9]])) if mask else None
    before = {name: _builds(name) for name in ("window", "unmasked", "masked")}

    def flash(q, k, v):
        return flash_attention(q, k, v, kv_mask=m, causal=True, block_q=block, block_k=block,
                               window=window)

    got = jax.value_and_grad(lambda *a: jnp.sum(flash(*a) * target), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(reference_attention(
        *a, kv_mask=m, causal=True, window=window) * target), argnums=(0, 1, 2))(q, k, v)
    assert {name: _builds(name) - n for name, n in before.items()} \
        == {"window": 1, "unmasked": 0, "masked": 0}
    assert float(got[0]) == pytest.approx(float(want[0]), rel=FLASH_LOSS_REL)
    for name, a, b in zip("qkv", got[1], want[1]):
        assert float(jnp.abs(a - b).max()) <= FLASH_GRAD_REL * float(jnp.abs(b).max()), name
    # the einsum core of the model under the band's mask gives the same
    causal = reference_attention(q, k, v, kv_mask=m, causal=True)
    assert float(jnp.abs(causal - flash(q, k, v)).max()) > 1e-2 or window >= t


def test_a_band_one_key_off_fails_the_tolerance():
    q, k, v, target = _qkv(100)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, window=24)
    off = reference_attention(q, k, v, causal=True, window=23)
    assert float(jnp.abs(got - off).max()) > 100 * FLASH_GRAD_REL


def test_the_windowed_unmasked_kernel_visits_a_rows_own_key_first():
    """Its step tables list each query block's key blocks from the diagonal
    down, so the kernel starts a row where its own key is and needs no guard:
    the unmasked variant's rows whose band misses a whole key block come out
    as the reference's (no NaN, no count of masked keys)."""
    q_blks, kv_blks = _work_steps(3, 3, 128, 128, True, 200)
    assert list(zip(q_blks.tolist(), kv_blks.tolist())) == [
        (0, 0), (1, 1), (1, 0), (2, 2), (2, 1), (2, 0)]
    q, k, v, _ = _qkv(384, heads=1)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, window=200)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference_attention(
        q, k, v, causal=True, window=200)), atol=2e-5)


def test_the_step_tables_at_the_cells_shape_visit_the_bands_tiles_and_no_others():
    """T 8192, blocks of 128, window 512: the tables list exactly the tiles
    that hold a pair i - 512 < j <= i, each once, query blocks in order; the
    gauge reads their pairs over the band's. Without a window the tables are
    the causal ones as they were: each row's blocks from 0 to the diagonal."""
    n, b, w = 64, 128, 512
    q_blks, kv_blks = _work_steps(n, n, b, b, True, w)
    steps = list(zip(q_blks.tolist(), kv_blks.tolist()))
    band = {(i, j) for i in range(n) for j in range(n)
            if j * b <= (i + 1) * b - 1 and (j + 1) * b - 1 > i * b - w}
    assert len(steps) == len(set(steps)) == len(band) == 310 and set(steps) == band
    assert q_blks.tolist() == sorted(q_blks.tolist())
    assert all(kv_blks[s] > kv_blks[s + 1] for s in range(len(steps) - 1)
               if q_blks[s] == q_blks[s + 1])             # backwards within a row
    assert (q_blks.dtype, kv_blks.dtype) == (np.int32, np.int32)
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert len(steps) * b * b / pairs == pytest.approx(1.2499, abs=1e-4)
    assert len(_work_steps(16, 16, 512, 512, True, w)[0]) * 512 * 512 / pairs \
        == pytest.approx(2.0, abs=0.01)
    # window 0: today's tables, element for element
    for causal in (True, False):
        was = [(i, j) for i in range(n) for j in range(n) if not causal or j <= i]
        for got in (_work_steps(n, n, b, b, causal), _work_steps(n, n, b, b, causal, 0)):
            assert list(zip(got[0].tolist(), got[1].tolist())) == was
    # and the gauge a traced windowed call sets
    q = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(q, q, q, causal=True, block_q=b, block_k=b,
                                             window=w), q)
    assert obs.get_registry().snapshot()["synapseml_attn_window_pairs_ratio"] \
        == pytest.approx(310 * b * b / pairs)


def test_a_call_without_a_window_builds_what_it_built_before():
    """`window=0` is the default: the same jaxpr, kernel and scope as a call
    that names no window, and no build counted as a window's."""
    q, k, v, _ = _qkv(256, heads=1)
    before = _builds("window")
    for block in (128, 16):
        plain = jax.make_jaxpr(lambda *a: flash_attention(*a, causal=True, block_q=block,
                                                          block_k=block))(q, k, v)
        zero = jax.make_jaxpr(lambda *a: flash_attention(*a, causal=True, block_q=block,
                                                         block_k=block, window=0))(q, k, v)
        assert str(plain) == str(zero)
    assert _builds("window") == before
    text = jax.jit(lambda *a: flash_attention(*a, causal=True)).lower(q, k, v).as_text(
        debug_info=True)
    assert "attn.flash" in text and "attn.window" not in text
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)


# ---- the layer kinds ----------------------------------------------------------------

def tiny_lm_loss(remat=True):
    config = tiny_config()
    trainer, loss_of = trainer_loss(config, remat=remat)
    params = adapter.to_program(ref.init_params(ref.sizes(config), 3), config)
    batch = rows(3, 2, 32)
    return (lambda p: loss_of(p, batch)[0]), params


@pytest.mark.parametrize("kept,launches", [("output_and_lse", 1), ("nothing", 2)])
def test_a_step_launches_each_layers_kernel_once(kept, launches, monkeypatch):
    """Five layers, two full and three sliding: one kernel launch a layer and
    step when the remat keeps the op's output and log-sum-exp (the windowed
    call keeps them by the same names), two when it keeps nothing."""
    if kept == "nothing":
        keep_nothing(monkeypatch)
    loss_of, params = tiny_lm_loss(remat=True)
    eqns = pallas_eqns(jax.make_jaxpr(jax.grad(loss_of))(params).jaxpr)
    assert len(eqns) == 5 * launches


def test_sliding_layers_run_under_attn_window_and_full_ones_under_attn_flash():
    loss_of, params = tiny_lm_loss()
    text = jax.jit(loss_of).lower(params).as_text(debug_info=True)
    assert "attn.window" in text and "attn.flash" in text
    assert "attn.flash/attn.window" not in text and "attn.window/attn.flash" not in text


def test_the_tree_holds_each_kinds_heads_and_a_gate_a_head():
    config = tiny_config()
    module = float32_module(config)
    made = module.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    shape = lambda t: jax.tree.map(np.shape, jax.tree.map(  # noqa: E731
        lambda v: getattr(v, "value", v), t, is_leaf=lambda v: hasattr(v, "names")))
    dec = shape(made)["decoder"]
    assert dec["layer_0"]["attn"] == {"q": {"kernel": (32, 6, 8)}, "k": {"kernel": (32, 2, 8)},
                                      "v": {"kernel": (32, 2, 8)}, "gate": {"kernel": (32, 6)},
                                      "o": {"kernel": (6, 8, 32)}}
    assert dec["layer_1"]["attn"]["q"] == {"kernel": (32, 8, 8)}
    assert dec["layer_1"]["attn"]["gate"] == {"kernel": (32, 8)}
    assert set(dec["layer_0"]["mlp"]) == {"gate", "up", "down"}
    assert set(dec["layer_1"]["mlp"]) == {"router", "w_gate", "w_up", "w_dn", "shared"}
    back = adapter.from_program(adapter.to_program(ref.init_params(ref.sizes(config), 1), config),
                                config)
    assert jax.tree.map(np.shape, back) == jax.tree.map(np.shape, ref.init_params(
        ref.sizes(config), 1))
    assert shape(adapter.to_program(ref.init_params(ref.sizes(config), 1), config)) \
        == shape(made)


def test_the_rotary_is_transformers_yarn_on_the_leading_dims():
    """cos and sin of the full layers' 64 rotary dims against
    `transformers`' `_compute_yarn_parameters` at the published settings, to
    float32 rounding of the angle (an ulp of the fastest frequency times the
    position)."""
    import types

    import torch
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    yarn = window_moe_lm().attn_kind(FULL).yarn
    hf = types.SimpleNamespace(
        rope_theta=500000.0, partial_rotary_factor=0.5, head_dim=128, hidden_size=2048,
        num_attention_heads=48, max_position_embeddings=262144,
        rope_scaling={"rope_type": "yarn", "factor": 64.0, "beta_fast": 64.0, "beta_slow": 1.0,
                      "original_max_position_embeddings": 4096,
                      "attention_factor": 1.4158883083359672})
    inv, factor = _compute_yarn_parameters(hf, "cpu")
    angle = torch.outer(torch.arange(8192, dtype=torch.float32), inv)
    cos, sin = rope_frequencies(64, 8192, 500000.0, yarn)
    assert cos.shape == (8192, 32) and factor == yarn.attention_factor
    np.testing.assert_allclose(cos, (angle.cos() * factor).numpy(), atol=2e-3)
    np.testing.assert_allclose(sin, (angle.sin() * factor).numpy(), atol=2e-3)
    np.testing.assert_allclose(cos[:512], (angle.cos() * factor).numpy()[:512], atol=1e-4)
    # without YaRN the table is the plain one: its first 6 dims keep their frequency
    plain, _ = rope_frequencies(64, 8192, 500000.0)
    np.testing.assert_allclose(cos[:, :6], plain[:, :6] * factor, atol=2e-3)
    assert np.abs(cos[:, 20:] - plain[:, 20:] * factor).max() > 0.1
    # a full layer turns its leading half alone, a sliding one every dim
    cfg = TransformerConfig(hidden=32, n_layers=2, n_heads=2, head_dim=16, causal=True,
                            use_rope=True, norm="rmsnorm", attn_bias=False, max_len=32,
                            dtype=jnp.float32, layer_types=(FULL, SLIDING),
                            attn_kinds=((FULL, AttnKind(rotary_fraction=0.5, yarn=yarn)),
                                        (SLIDING, AttnKind(window=4, rope_theta=10000.0))))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 32))
    base = jnp.broadcast_to(jnp.arange(12)[None], (1, 12))
    for kind, turned in ((FULL, 8), (SLIDING, 16)):
        spy = SpyAttention(cfg, kind=kind)
        params = spy.init(jax.random.PRNGKey(1), x)
        (out0, q0, k0), (out1, q1, k1) = (spy_qk(spy, params, x, pos) for pos in (base, base + 5))
        for a, b in ((q0, q1), (k0, k1)):
            assert np.array_equal(np.asarray(a[..., turned:]), np.asarray(b[..., turned:]))
            assert np.abs(np.asarray(a[..., :turned] - b[..., :turned])).max() > 1e-3
        # relative positions alone reach the scores: a common shift keeps the output
        np.testing.assert_allclose(np.asarray(out0), np.asarray(out1), rtol=1e-4, atol=1e-5)


class SpyAttention(Attention):
    """The attention module, keeping the queries and keys it hands to its core."""

    def _attend(self, q, k, v, mask, window=0):
        self.sow("intermediates", "qk", (q, k))
        return super()._attend(q, k, v, mask, window)


def spy_qk(spy, params, x, positions):
    out, sown = spy.apply(params, x, None, positions, mutable=["intermediates"])
    return (out, *sown["intermediates"]["qk"][0])


def test_the_shares_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Eight chips of two experts each, the softmax router over all sixteen
    with its gates scaled by 2.5: the eight shares' results, with the shared
    expert (computed on every chip) counted once, are the uncut layer's."""
    config = tiny_config(num_experts=2, published_num_experts=16, expert_share="0 of 8")
    sizes = ref.sizes(config)
    whole = dict(sizes, held=16, first_expert=0)
    lp = ref.layer_params(ref.init_params(whole, 9), 1)
    f = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_ffn(whole, "float32", lp, f, {})       # shared expert included
        shared = ref.shared_expert("float32", lp, f)
    assert float(jnp.abs(shared).max()) > 0.1 * float(jnp.abs(uncut).max())
    cfg = float32_module(config).cfg
    assert (cfg.moe_router, cfg.moe_gate_scale, cfg.moe_experts) == ("softmax", 2.5, 2)
    shared_params = {"gate": {"kernel": lp["sg"]}, "up": {"kernel": lp["su"]},
                     "down": {"kernel": lp["sd"]}}
    total, n_shares = jnp.zeros_like(uncut), 8
    for share in range(n_shares):
        c = dataclasses.replace(cfg, moe_first_expert=2 * share)
        part = {"router": {"kernel": lp["router"]}, "shared": shared_params,
                "w_gate": lp["wg"][2 * share:2 * share + 2],
                "w_up": lp["wu"][2 * share:2 * share + 2],
                "w_dn": lp["wd"][2 * share:2 * share + 2]}
        total = total + MoEBlock(c).apply({"params": part}, f)
    total = total - (n_shares - 1) * shared             # every chip computed it: once
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=2e-5, atol=2e-6)


def test_the_softmax_routers_gates_sum_to_the_scale_and_a_scale_of_1_adds_nothing():
    cfg = TransformerConfig(hidden=16, n_layers=1, n_heads=2, mlp_dim=8, moe_mlp_dim=12,
                            gated_mlp=True, act="silu", mlp_bias=False, moe_experts=8,
                            moe_top_k=3, moe_dispatch="grouped", moe_bias=False,
                            moe_gate_scale=2.5, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 16))
    params = MoEBlock(cfg).init(jax.random.PRNGKey(1), x)

    def out(c):
        return MoEBlock(c).apply(params, x)

    unscaled = dataclasses.replace(cfg, moe_gate_scale=1.0)
    # every expert held: the routed result is linear in the gates
    np.testing.assert_allclose(np.asarray(out(cfg)), 2.5 * np.asarray(out(unscaled)),
                               rtol=1e-5, atol=1e-6)

    def muls(c):
        jaxpr = jax.make_jaxpr(lambda p: MoEBlock(c).apply(p, x))(params)
        return str(jaxpr).count(" mul ")

    assert muls(cfg) == muls(unscaled) + 1


# ---- the configuration ---------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    ({"layer_types": (FULL, "local_attention")}, "layer_types"),
    ({"layer_types": (FULL, SLIDING)}, "window"),
    ({"layer_types": (FULL, SLIDING), "attn_kinds": ((SLIDING, AttnKind(window=4)),),
      "causal": False}, "causal"),
    ({"attn_kinds": (("conv", AttnKind(window=4)),)}, "attn_kinds")],
    ids=["unknown_kind", "sliding_without_a_window", "sliding_not_causal", "kind_of_no_attention"])
def test_the_config_refuses_what_it_cannot_run(kw, match):
    base = dict(hidden=32, n_layers=2, n_heads=2, causal=True)
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**base)


def test_a_sliding_layer_has_no_decode_cache_and_the_builder_is_the_published_one():
    cfg = window_moe_lm(vocab_size=64, hidden=32, n_layers=2, n_heads=4, n_kv_heads=2,
                        head_dim=8, mlp_dim=16, moe_mlp_dim=8, moe_shared_mlp_dim=8,
                        moe_experts=4, moe_total_experts=4, moe_top_k=2, max_len=32,
                        attn_impl="einsum",
                        attn_kinds=((SLIDING, AttnKind(heads=8, window=4)),))
    assert cfg.layer_types == (FULL, SLIDING)
    ids = np.zeros((1, 8), np.int32)
    LlamaLM(cfg).init(jax.random.PRNGKey(0), ids)         # training and prefill build
    with pytest.raises(ValueError, match="'sliding_attention' layer has no decode cache"):
        LlamaLM(cfg, decode=True).init(jax.random.PRNGKey(0), ids)
    # the full layer alone still decodes through its cache
    full = dataclasses.replace(cfg, n_layers=1, layer_types=(FULL,))
    LlamaLM(full, decode=True).init(jax.random.PRNGKey(0), ids)
    published = window_moe_lm()
    assert (published.n_layers, published.hidden, published.n_heads, published.kv_heads,
            published.head_dim, published.mlp_dim, published.moe_mlp_dim,
            published.moe_shared_mlp_dim, published.moe_experts, published.moe_top_k,
            published.vocab_size, published.moe_gate_scale, published.moe_router) \
        == (40, 2048, 48, 8, 128, 8192, 512, 512, 256, 8, 100352, 2.5, "softmax")
    assert published.layer_types.count(FULL) == 10 and published.layer_types[::4] == (FULL,) * 10
    sliding, full = published.attn_kind(SLIDING), published.attn_kind(FULL)
    assert (sliding.heads, sliding.window, sliding.rope_theta, sliding.rotary_fraction,
            sliding.yarn) == (64, 512, 10000.0, 1.0, None)
    assert (full.heads, full.window, full.rotary_fraction, published.rope_theta,
            full.yarn) == (0, 0, 0.5, 500000.0, Yarn(64.0, 4096, 64.0, 1.0, 1.4158883083359672))
