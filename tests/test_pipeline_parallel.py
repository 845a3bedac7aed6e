"""Pipeline parallelism over the `pipe` mesh axis (GPipe schedule with
ppermute activation rotation) vs the sequential stage-chain oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.parallel import MeshConfig, create_mesh
from synapseml_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_sharded,
    stack_stage_params,
)


def mlp_stage(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def make_stages(n_stages, d, seed=0):
    rs = np.random.default_rng(seed)
    return [{"w": jnp.asarray(rs.normal(size=(d, d)) * 0.4, jnp.float32),
             "b": jnp.asarray(rs.normal(size=(d,)) * 0.1, jnp.float32)}
            for _ in range(n_stages)]


def sequential(stages, x_micro):
    y = x_micro
    for p in stages:
        y = jax.vmap(lambda x, p=p: mlp_stage(p, x))(y)
    return y


@pytest.mark.parametrize("n_micro", [1, 4, 8])
def test_pipeline_matches_sequential(n_micro):
    n_stages, mb, d = 4, 3, 8
    stages = make_stages(n_stages, d)
    stacked = stack_stage_params(stages)
    rs = np.random.default_rng(1)
    x = jnp.asarray(rs.normal(size=(n_micro, mb, d)), jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out = pipeline_sharded(mesh, mlp_stage, stacked, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_gradients_match_sequential():
    n_stages, n_micro, mb, d = 4, 6, 2, 8
    stages = make_stages(n_stages, d, seed=2)
    stacked = stack_stage_params(stages)
    rs = np.random.default_rng(3)
    x = jnp.asarray(rs.normal(size=(n_micro, mb, d)), jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))

    def loss_pp(params):
        return jnp.sum(pipeline_sharded(mesh, mlp_stage, params, x) ** 2)

    def loss_seq(params):
        y = x
        for s in range(n_stages):
            p = jax.tree.map(lambda q: q[s], params)
            y = jax.vmap(lambda xx, p=p: mlp_stage(p, xx))(y)
        return jnp.sum(y ** 2)

    g_pp = jax.grad(loss_pp)(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    for a, b in zip(jax.tree.leaves(g_seq), jax.tree.leaves(g_pp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_jit_and_pipe_times_data_mesh():
    # composition: pipe=2 x data=4, jitted end-to-end
    n_stages, n_micro, mb, d = 2, 5, 2, 4
    stages = make_stages(n_stages, d, seed=4)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=4, pipe=2))
    out = jax.jit(lambda p, xx: pipeline_sharded(mesh, mlp_stage, p, xx))(
        stacked, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_stage_count_mismatch_rejected():
    stages = make_stages(8, 4, seed=10)  # 8 stages on a pipe=4 axis
    stacked = stack_stage_params(stages)
    x = jnp.zeros((2, 2, 4), jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    with pytest.raises(ValueError, match="one stage per device"):
        pipeline_sharded(mesh, mlp_stage, stacked, x)


def test_pipeline_fallback_without_pipe_axis():
    stages = make_stages(3, 4, seed=6)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 2, 4)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=-1))  # no pipe axis
    out = pipeline_sharded(mesh, mlp_stage, stacked, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-6)


def test_pipeline_inside_shard_map_direct():
    # the collective form composes with a manual shard_map call site
    from jax.sharding import PartitionSpec as P

    n_stages, n_micro, mb, d = 8, 3, 2, 4
    stages = make_stages(n_stages, d, seed=8)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=1, pipe=8))
    mapped = jax.shard_map(
        lambda p, xx: pipeline_apply(mlp_stage, p, xx),
        mesh=mesh.mesh,
        in_specs=(jax.tree.map(lambda _: P("pipe"), stacked), P()),
        out_specs=P())
    np.testing.assert_allclose(np.asarray(mapped(stacked, x)),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_remat_gradients_match():
    # jax.checkpoint on the stage fn: same grads, recomputed activations
    n_stages, n_micro, mb, d = 4, 4, 2, 8
    stages = make_stages(n_stages, d, seed=12)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(13).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))

    def loss(params, remat):
        return jnp.sum(pipeline_sharded(mesh, mlp_stage, params, x,
                                        remat=remat) ** 2)

    g_plain = jax.grad(lambda p: loss(p, False))(stacked)
    g_remat = jax.grad(lambda p: loss(p, True))(stacked)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_pipeline_pytree_payload_carries_mask():
    """Stages may pipe PYTREE payloads: (hidden, mask) travel together, the
    stage transforms hidden under its mask and passes the mask through —
    the transformer-block shape of pipelining."""
    n_stages, n_micro, mb, d = 4, 5, 3, 8
    stages = make_stages(n_stages, d, seed=14)
    stacked = stack_stage_params(stages)
    rs = np.random.default_rng(15)
    x = jnp.asarray(rs.normal(size=(n_micro, mb, d)), jnp.float32)
    mask = jnp.asarray(rs.random((n_micro, mb, d)) > 0.3, jnp.float32)

    def masked_stage(p, payload):
        h, m = payload
        return jnp.tanh((h * m) @ p["w"] + p["b"]), m

    def seq(x, mask):
        y = x
        for p in stages:
            y, _ = jax.vmap(lambda h, m, p=p: masked_stage(p, (h, m)))(y, mask)
        return y

    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out_h, out_m = pipeline_sharded(mesh, masked_stage, stacked, (x, mask))
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(seq(x, mask)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out_m), np.asarray(mask))


@pytest.mark.parametrize("n_micro", [4, 8])
def test_pipeline_sharded_io_matches_sequential(n_micro):
    # io='sharded': microbatches in AND out live sharded over pipe
    n_stages, mb, d = 4, 3, 8
    stages = make_stages(n_stages, d)
    stacked = stack_stage_params(stages)
    rs = np.random.default_rng(21)
    x = jnp.asarray(rs.normal(size=(n_micro, mb, d)), jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out = pipeline_sharded(mesh, mlp_stage, stacked, x, io="sharded")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_sharded_io_gradients_match():
    n_stages, n_micro, mb, d = 4, 8, 2, 8
    stages = make_stages(n_stages, d, seed=22)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(23).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))

    def loss(params, io):
        return jnp.sum(pipeline_sharded(mesh, mlp_stage, params, x,
                                        io=io) ** 2)

    g_rep = jax.grad(lambda p: loss(p, "replicated"))(stacked)
    g_shd = jax.grad(lambda p: loss(p, "sharded"))(stacked)
    for a, b in zip(jax.tree.leaves(g_rep), jax.tree.leaves(g_shd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_sharded_io_memory_scales_inverse_with_stages():
    """The 1/S memory contract: with io='sharded' each device addresses only
    n_micro/S microbatches of the output (and the schedule's carry holds
    O(chunk) slots), vs the replicated layout's full n_micro everywhere."""
    n_stages, n_micro, mb, d = 4, 8, 2, 8
    stages = make_stages(n_stages, d, seed=24)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(25).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    assert dict(mesh.mesh.shape)["pipe"] == n_stages  # not the seq fallback
    with mesh.scope():
        out_s = jax.jit(lambda p, xx: pipeline_sharded(
            mesh, mlp_stage, p, xx, io="sharded"))(stacked, x)
        out_r = jax.jit(lambda p, xx: pipeline_sharded(
            mesh, mlp_stage, p, xx, io="replicated"))(stacked, x)
    # per-device shard of the sharded output is 1/S of the microbatches
    shard_shapes = {s.data.shape for s in out_s.addressable_shards}
    assert shard_shapes == {(n_micro // n_stages, mb, d)}, shard_shapes
    # the replicated layout holds ALL microbatches on every device
    assert {s.data.shape for s in out_r.addressable_shards} \
        == {(n_micro, mb, d)}
    # and the compiled per-device program's live buffers reflect it when the
    # backend reports memory analysis (probing guarded — the assert is not)
    out_sz_s = out_sz_r = 0
    try:
        lowered_s = jax.jit(lambda p, xx: pipeline_sharded(
            mesh, mlp_stage, p, xx, io="sharded")).lower(stacked, x)
        lowered_r = jax.jit(lambda p, xx: pipeline_sharded(
            mesh, mlp_stage, p, xx, io="replicated")).lower(stacked, x)
        ma_s = lowered_s.compile().memory_analysis()
        ma_r = lowered_r.compile().memory_analysis()
        out_sz_s = getattr(ma_s, "output_size_in_bytes", 0)
        out_sz_r = getattr(ma_r, "output_size_in_bytes", 0)
    except (NotImplementedError, AttributeError, RuntimeError):
        pass  # backend without memory analysis: shard-shape assertions above
    if out_sz_s and out_sz_r:
        assert out_sz_s <= out_sz_r, (out_sz_s, out_sz_r)


def test_pipeline_sharded_io_pytree_payload():
    n_stages, n_micro, mb, d = 4, 4, 3, 8
    stages = make_stages(n_stages, d, seed=26)
    stacked = stack_stage_params(stages)
    rs = np.random.default_rng(27)
    x = jnp.asarray(rs.normal(size=(n_micro, mb, d)), jnp.float32)
    mask = jnp.asarray(rs.random((n_micro, mb, d)) > 0.3, jnp.float32)

    def masked_stage(p, payload):
        h, m = payload
        return jnp.tanh((h * m) @ p["w"] + p["b"]), m

    def seq(x, mask):
        y = x
        for p in stages:
            y, _ = jax.vmap(lambda h, m, p=p: masked_stage(p, (h, m)))(y, mask)
        return y

    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out_h, out_m = pipeline_sharded(mesh, masked_stage, stacked, (x, mask),
                                    io="sharded")
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(seq(x, mask)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out_m), np.asarray(mask))


def test_pipeline_sharded_io_rejects_indivisible():
    stages = make_stages(4, 4, seed=28)
    stacked = stack_stage_params(stages)
    x = jnp.zeros((6, 2, 4), jnp.float32)  # 6 % 4 != 0
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    with pytest.raises(ValueError, match="divisible"):
        pipeline_sharded(mesh, mlp_stage, stacked, x, io="sharded")


@pytest.mark.parametrize("n_micro", [4, 8])
def test_pipeline_interleaved_matches_sequential(n_micro):
    # circular schedule: 8 stages round-robin on pipe=4 (v=2)
    n_stages, mb, d = 8, 3, 8
    stages = make_stages(n_stages, d, seed=31)
    stacked = stack_stage_params(stages)
    rs = np.random.default_rng(32)
    x = jnp.asarray(rs.normal(size=(n_micro, mb, d)), jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out = pipeline_sharded(mesh, mlp_stage, stacked, x, interleave=2)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_interleaved_gradients_match_sequential():
    n_stages, n_micro, mb, d = 8, 8, 2, 8
    stages = make_stages(n_stages, d, seed=33)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(34).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))

    def loss_pp(params):
        return jnp.sum(pipeline_sharded(mesh, mlp_stage, params, x,
                                        interleave=2) ** 2)

    def loss_seq(params):
        y = x
        for s in range(n_stages):
            p = jax.tree.map(lambda q: q[s], params)
            y = jax.vmap(lambda xx, p=p: mlp_stage(p, xx))(y)
        return jnp.sum(y ** 2)

    g_pp = jax.grad(loss_pp)(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    for a, b in zip(jax.tree.leaves(g_seq), jax.tree.leaves(g_pp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_interleaved_deeper_chunks():
    # v=4: 8 stages on pipe=2, jitted, payload wraps three times
    n_stages, n_micro, mb, d = 8, 6, 2, 4
    stages = make_stages(n_stages, d, seed=35)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(36).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=4, pipe=2))
    out = jax.jit(lambda p, xx: pipeline_sharded(
        mesh, mlp_stage, p, xx, interleave=4))(stacked, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential(stages, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_interleaved_remat_gradients_match():
    n_stages, n_micro, mb, d = 8, 4, 2, 8
    stages = make_stages(n_stages, d, seed=38)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(np.random.default_rng(39).normal(size=(n_micro, mb, d)),
                    jnp.float32)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))

    def loss(params, remat):
        return jnp.sum(pipeline_sharded(mesh, mlp_stage, params, x,
                                        interleave=2, remat=remat) ** 2)

    g_plain = jax.grad(lambda p: loss(p, False))(stacked)
    g_remat = jax.grad(lambda p: loss(p, True))(stacked)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_pipeline_interleaved_real_transformer_blocks():
    """Eight REAL transformer Blocks on pipe=4 with v=2 round-robin chunks:
    the circular schedule must match the sequential Encoder chain with the
    attention mask riding the payload."""
    from flax.core import meta

    from synapseml_tpu.models.flax_nets.transformer import (Block,
                                                            TransformerConfig)

    cfg = TransformerConfig(hidden=16, n_layers=8, n_heads=2, mlp_dim=32,
                            max_len=16, dtype=jnp.float32)
    block = Block(cfg)
    rs = np.random.default_rng(40)
    n_micro, mb, T = 4, 2, 8
    x = jnp.asarray(rs.normal(size=(n_micro, mb, T, cfg.hidden)), jnp.float32)
    mask_rows = rs.random((n_micro, mb, T)) > 0.2
    mask = jnp.asarray(mask_rows[:, :, None, None, :])

    layer_params = []
    for i in range(8):
        v = block.init(jax.random.PRNGKey(i), x[0], mask[0])
        layer_params.append(meta.unbox(v)["params"])
    stacked = stack_stage_params(layer_params)

    def stage(p, payload):
        h, m = payload
        return block.apply({"params": p}, h, m), m

    def sequential_blocks(xs, ms):
        y = xs
        for p in layer_params:
            y = jnp.stack([block.apply({"params": p}, y[i], ms[i])
                           for i in range(n_micro)])
        return y

    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out, _ = pipeline_sharded(mesh, stage, stacked, (x, mask), interleave=2)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential_blocks(x, mask)),
                               rtol=2e-4, atol=2e-5)


def test_pipeline_interleaved_rejections():
    stages = make_stages(8, 4, seed=37)
    stacked = stack_stage_params(stages)
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    with pytest.raises(ValueError, match="divisible"):
        pipeline_sharded(mesh, mlp_stage, stacked,
                         jnp.zeros((6, 2, 4), jnp.float32), interleave=2)
    with pytest.raises(ValueError, match="pipe\\*interleave"):
        pipeline_sharded(mesh, mlp_stage, stacked,
                         jnp.zeros((8, 2, 4), jnp.float32), interleave=3)
    with pytest.raises(ValueError, match="io='replicated'"):
        pipeline_sharded(mesh, mlp_stage, stacked,
                         jnp.zeros((8, 2, 4), jnp.float32), interleave=2,
                         io="sharded")


def test_pipeline_real_transformer_blocks():
    """REAL transformer Blocks through the pipeline: an Encoder's per-layer
    params restack into stages, each stage applies its Block with the
    attention mask riding the payload — outputs match the sequential
    Encoder apply exactly."""
    from flax.core import meta

    from synapseml_tpu.models.flax_nets.transformer import (Block,
                                                            TransformerConfig)

    cfg = TransformerConfig(hidden=16, n_layers=4, n_heads=2, mlp_dim=32,
                            max_len=16, dtype=jnp.float32)
    block = Block(cfg)
    rs = np.random.default_rng(16)
    n_micro, mb, T = 4, 2, 8
    x = jnp.asarray(rs.normal(size=(n_micro, mb, T, cfg.hidden)), jnp.float32)
    mask_rows = rs.random((n_micro, mb, T)) > 0.2
    mask = jnp.asarray(mask_rows[:, :, None, None, :])  # [nm, mb, 1, 1, T]

    layer_params = []
    for i in range(4):
        v = block.init(jax.random.PRNGKey(i), x[0], mask[0])
        layer_params.append(meta.unbox(v)["params"])
    stacked = stack_stage_params(layer_params)

    def stage(p, payload):
        h, m = payload
        return block.apply({"params": p}, h, m), m

    def sequential_blocks(xs, ms):
        y = xs
        for p in layer_params:
            y = jnp.stack([block.apply({"params": p}, y[i], ms[i])
                           for i in range(n_micro)])
        return y

    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    out, _ = pipeline_sharded(mesh, stage, stacked, (x, mask))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sequential_blocks(x, mask)),
                               rtol=2e-4, atol=2e-5)
