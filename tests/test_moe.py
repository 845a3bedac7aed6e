"""Mixture-of-experts MLP (switch routing, capacity-bucketed einsum
dispatch, expert-parallel sharding over the `expert` mesh axis) vs a
per-token numpy oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.models.flax_nets.transformer import (
    Encoder,
    MoEBlock,
    TransformerConfig,
)
from synapseml_tpu.parallel import MeshConfig, create_mesh
from synapseml_tpu.parallel.mesh import shard_params


def cfg_with(**kw):
    base = dict(hidden=16, n_layers=1, n_heads=4, mlp_dim=32, max_len=16,
                dtype=jnp.float32, moe_experts=4, moe_capacity_factor=2.0)
    base.update(kw)
    return TransformerConfig(**base)


def moe_oracle(x, variables, cfg):
    """Per-token reference: route by top-k of the same router, apply the
    chosen experts densely, weight by normalized gates; capacity ignored
    (use a capacity factor large enough that nothing drops)."""
    from flax.core import meta

    p = meta.unbox(variables)["params"]
    S = x.shape[0] * x.shape[1]
    xf = np.asarray(x, np.float64).reshape(S, -1)
    logits = xf @ np.asarray(p["router"]["kernel"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    k = cfg.moe_top_k
    out = np.zeros_like(xf)
    from scipy.special import erf

    def gelu(v):
        return 0.5 * v * (1 + erf(v / np.sqrt(2)))

    for s in range(S):
        idx = np.argsort(-probs[s])[:k]
        gates = probs[s][idx]
        gates = gates / gates.sum() if k > 1 else gates
        for e, g in zip(idx, gates):
            h = gelu(xf[s] @ np.asarray(p["w_up"][e], np.float64)
                     + np.asarray(p["b_up"][e], np.float64))
            out[s] += g * (h @ np.asarray(p["w_dn"][e], np.float64)
                           + np.asarray(p["b_dn"][e], np.float64))
    return out.reshape(x.shape)


@pytest.mark.parametrize("dispatch", ["einsum", "scatter"])
@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_matches_per_token_oracle(top_k, dispatch):
    cfg = cfg_with(moe_top_k=top_k, moe_capacity_factor=8.0,  # no drops
                   moe_dispatch=dispatch)
    block = MoEBlock(cfg)
    rs = np.random.default_rng(0)
    x = jnp.asarray(rs.normal(size=(2, 6, 16)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)
    out = block.apply(variables, x)
    expect = moe_oracle(x, variables, cfg)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_moe_scatter_equals_einsum_dispatch():
    # same params, same input, both layouts, no drops: bitwise-equivalent
    # routing decisions must produce numerically matching outputs
    cfg_e = cfg_with(moe_top_k=2, moe_capacity_factor=8.0)
    cfg_s = dataclasses.replace(cfg_e, moe_dispatch="scatter")
    rs = np.random.default_rng(7)
    x = jnp.asarray(rs.normal(size=(2, 8, 16)), jnp.float32)
    variables = MoEBlock(cfg_e).init(jax.random.PRNGKey(0), x)
    out_e = np.asarray(MoEBlock(cfg_e).apply(variables, x))
    out_s = np.asarray(MoEBlock(cfg_s).apply(variables, x))
    np.testing.assert_allclose(out_s, out_e, rtol=1e-5, atol=1e-6)


def test_moe_scatter_equals_einsum_under_capacity_pressure():
    # k=2 with tight capacity: the two layouts must DROP THE SAME
    # assignments (choice-major fill priority — all first choices seat
    # before any second choice), not just agree in the no-drop regime
    cfg_e = cfg_with(moe_experts=2, moe_top_k=2, moe_capacity_factor=0.5)
    cfg_s = dataclasses.replace(cfg_e, moe_dispatch="scatter")
    rs = np.random.default_rng(11)
    x = jnp.asarray(rs.normal(size=(2, 8, 16)), jnp.float32)
    variables = MoEBlock(cfg_e).init(jax.random.PRNGKey(0), x)
    out_e = np.asarray(MoEBlock(cfg_e).apply(variables, x))
    out_s = np.asarray(MoEBlock(cfg_s).apply(variables, x))
    np.testing.assert_allclose(out_s, out_e, rtol=1e-5, atol=1e-6)


def test_moe_dispatch_validated():
    cfg = cfg_with(moe_dispatch="scater")
    block = MoEBlock(cfg)
    x = jnp.zeros((1, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match="moe_dispatch"):
        block.init(jax.random.PRNGKey(0), x)


def test_moe_scatter_capacity_drops_tokens():
    # the scatter layout honors the same switch drop semantics as einsum
    cfg = cfg_with(moe_experts=2, moe_capacity_factor=1e-9,
                   moe_dispatch="scatter")
    block = MoEBlock(cfg)
    rs = np.random.default_rng(1)
    x = jnp.asarray(rs.normal(size=(1, 8, 16)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)
    out = np.asarray(block.apply(variables, x))[0]
    nonzero_rows = np.sum(np.abs(out).sum(-1) > 1e-6)
    assert nonzero_rows <= 2, nonzero_rows


def test_moe_scatter_grads_flow():
    # the gather/scatter path must be differentiable end to end
    cfg = cfg_with(moe_top_k=2, moe_capacity_factor=8.0,
                   moe_dispatch="scatter")
    block = MoEBlock(cfg)
    rs = np.random.default_rng(9)
    x = jnp.asarray(rs.normal(size=(2, 4, 16)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)

    def loss(v):
        return jnp.sum(block.apply(v, x) ** 2)

    g = jax.grad(loss)(variables)
    flat = jax.tree.leaves(jax.tree.map(lambda a: float(jnp.abs(a).sum()),
                                        g["params"]))
    assert all(np.isfinite(v) for v in flat)
    assert sum(flat) > 0.0


def test_moe_capacity_drops_tokens():
    # capacity 1 token/expert: overflowing tokens contribute ZERO (switch
    # drop semantics — the block's residual carries them)
    cfg = cfg_with(moe_experts=2, moe_capacity_factor=1e-9)
    block = MoEBlock(cfg)
    rs = np.random.default_rng(1)
    x = jnp.asarray(rs.normal(size=(1, 8, 16)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)
    out = np.asarray(block.apply(variables, x))[0]
    # with C=1, at most 2 tokens (one per expert) produce nonzero output
    nonzero_rows = np.sum(np.abs(out).sum(-1) > 1e-6)
    assert nonzero_rows <= 2, nonzero_rows


def test_moe_aux_loss_sown():
    cfg = cfg_with()
    block = MoEBlock(cfg)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 4, 16)),
                    jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)
    _, state = block.apply(variables, x, mutable=["intermediates"])
    (aux,) = state["intermediates"]["moe_aux_loss"]
    assert float(aux) > 0.0  # E * sum(f*P) >= 1 at balance, > 0 always


def test_moe_expert_parallel_matches_unsharded():
    cfg = cfg_with(moe_capacity_factor=8.0)
    block = MoEBlock(cfg)
    rs = np.random.default_rng(3)
    x = jnp.asarray(rs.normal(size=(2, 8, 16)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(1), x)
    ref = np.asarray(block.apply(variables, x))

    mesh = create_mesh(MeshConfig(data=2, expert=4))
    placed = shard_params(variables, mesh)
    with mesh.scope():
        out = jax.jit(lambda v, xx: block.apply(v, xx))(placed, x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_moe_scatter_expert_parallel_matches_unsharded():
    # the HF/Mixtral dispatch layout under a sharded expert axis: GSPMD must
    # reshard the scatter/gather traffic without changing results
    cfg = cfg_with(moe_top_k=2, moe_capacity_factor=8.0,
                   moe_dispatch="scatter")
    block = MoEBlock(cfg)
    rs = np.random.default_rng(13)
    x = jnp.asarray(rs.normal(size=(2, 8, 16)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(1), x)
    ref = np.asarray(block.apply(variables, x))

    mesh = create_mesh(MeshConfig(data=2, expert=4))
    placed = shard_params(variables, mesh)
    with mesh.scope():
        out = jax.jit(lambda v, xx: block.apply(v, xx))(placed, x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_moe_encoder_trains():
    # gradient flow end-to-end: a 2-layer MoE encoder fits a tiny regression
    cfg = cfg_with(n_layers=2, moe_top_k=2)
    enc = Encoder(cfg)
    rs = np.random.default_rng(4)
    x = jnp.asarray(rs.normal(size=(4, 8, 16)), jnp.float32)
    y = jnp.asarray(rs.normal(size=(4, 8, 16)), jnp.float32)
    variables = enc.init(jax.random.PRNGKey(0), x)

    @jax.jit
    def step(params):
        def loss(p):
            out = enc.apply({"params": p}, x)
            return jnp.mean((out - y) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        return jax.tree.map(lambda a, b: a - 0.05 * b, params, g), l

    params = variables["params"]
    losses = []
    for _ in range(5):
        params, l = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(l) for l in losses)


def test_trainer_applies_moe_aux_loss():
    # the Trainer must fold the sown load-balance term into the training
    # loss — a zero vs nonzero moe_aux_weight must change the loss value
    from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_tiny
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig

    cfg = bert_tiny(n_layers=1, moe_experts=2, moe_top_k=1)
    rs = np.random.default_rng(0)
    batch = {"input_ids": rs.integers(0, cfg.vocab_size, (8, 8)).astype(np.int32),
             "attention_mask": np.ones((8, 8), np.int32),
             "labels": rs.integers(0, 2, (8,)).astype(np.int32)}
    mesh = create_mesh(MeshConfig(data=-1))

    def loss_with(weight):
        tr = Trainer(BertClassifier(cfg, num_classes=2), mesh,
                     TrainerConfig(learning_rate=1e-3, total_steps=4,
                                   moe_aux_weight=weight))
        state = tr.init_state(batch)
        _, metrics = tr.train_step(state, batch)
        return float(metrics["loss"])

    l0, l1 = loss_with(0.0), loss_with(0.5)
    assert l1 > l0, (l0, l1)  # aux term is positive, so it must show up


def test_dense_mlp_unchanged_when_moe_disabled():
    cfg = cfg_with(moe_experts=0)
    enc = Encoder(cfg)
    x = jnp.zeros((1, 4, 16), jnp.float32)
    variables = enc.init(jax.random.PRNGKey(0), x)
    names = set(variables["params"]["layer_0"]["mlp"].keys())
    assert "router" not in names and "up" in names  # plain MlpBlock params
