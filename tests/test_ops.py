"""ops module: flash attention (Pallas, interpret on CPU) + ring attention
(shard_map over the 8-device seq mesh) vs the XLA reference oracle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.core import observability as obs
from synapseml_tpu.ops import attention, flash_attention, reference_attention, ring_attention_sharded
from synapseml_tpu.ops.attention import _work_steps
from synapseml_tpu.parallel import MeshConfig, create_mesh


def make_qkv(B=2, T=64, H=4, D=32, seed=0):
    rs = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.float32) for _ in range(3))
    mask = jnp.asarray(rs.random((B, T)) > 0.2)
    return q, k, v, mask


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_mask", [False, True])
def test_flash_matches_reference(causal, with_mask):
    q, k, v, mask = make_qkv()
    kv_mask = mask if with_mask else None
    ref = reference_attention(q, k, v, kv_mask=kv_mask, causal=causal)
    out = flash_attention(q, k, v, kv_mask=kv_mask, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_flash_gradients_match():
    q, k, v, mask = make_qkv()

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, kv_mask=mask, causal=True) ** 2)

    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(loss(lambda *a, **kw: flash_attention(*a, block_q=16, block_k=16, **kw)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_bf16_matches_f32_reference():
    # the MXU training path: bf16 q/k/v, dots in bf16 with f32 accumulation
    # (NOT pre-upcast to f32 — that would hit the ~4x slower f32 MXU path).
    # Values and grads must track the f32 oracle within bf16 resolution.
    # (small T keeps this in the fast default lane; shape/pad coverage lives
    # in the f32 tests above)
    q, k, v, mask = make_qkv(T=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=True)
    out = flash_attention(qb, kb, vb, kv_mask=mask, causal=True,
                          block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ref),
                               np.asarray(out, dtype=np.float32), atol=3e-2)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, kv_mask=mask, causal=True).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(loss(lambda *a, **kw: flash_attention(
        *a, block_q=16, block_k=16, **kw)), argnums=(0, 1, 2))(qb, kb, vb)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(b, dtype=np.float32),
                                   atol=0.15, rtol=0.05)


def test_flash_unaligned_shapes():
    # T not a multiple of the block, D not a multiple of 128: pad/slice path
    q, k, v, _ = make_qkv(T=50, D=24)
    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_fully_masked_rows_zero():
    q, k, v, _ = make_qkv(T=16)
    mask = jnp.zeros((2, 16), bool).at[:, :4].set(True)
    # causal+mask: no fully masked rows among the first 4, rows attending only
    # to masked positions produce exactly zero
    out = flash_attention(q, k, v, kv_mask=mask, block_q=8, block_k=8)
    ref = reference_attention(q, k, v, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)
    all_masked = jnp.zeros((2, 16), bool)
    out0 = flash_attention(q, k, v, kv_mask=all_masked, block_q=8, block_k=8)
    assert float(jnp.max(jnp.abs(out0))) == 0.0


def _kernel_builds() -> dict:
    snap = obs.get_registry().snapshot()
    return {v: snap.get('synapseml_flash_kernel_builds_total{variant="%s"}' % v, 0.0)
            for v in ("unmasked", "masked")}


def _built_since(before: dict) -> dict:
    return {v: n - before[v] for v, n in _kernel_builds().items()}


def _within_one_ulp(a, b, dtype):
    """|a - b| is at most one unit in the last place of ``dtype`` at the larger
    of the two magnitudes (exact equality for float32 is asserted apart)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    mag = np.maximum(np.maximum(np.abs(a), np.abs(b)), np.finfo(np.float32).tiny)
    ulp = float(jnp.finfo(dtype).eps) * 2.0 ** np.floor(np.log2(mag))
    return bool(np.all(np.abs(a - b) <= ulp))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_without_a_mask_equals_flash_with_an_all_ones_mask(causal, block_q, block_k,
                                                                   D, dtype):
    """The unmasked variant (no mask operand, no guard, only the steps that hold
    work, lane-replicated row statistics) against the masked one, which is the
    kernel every call ran before: output and the three gradients."""
    T = 512                                     # 2..4 blocks a side
    q, k, v, _ = make_qkv(B=1, T=T, H=2, D=D, seed=D + block_q)
    ones = jnp.ones((1, T), bool)

    def run(fn, kv_mask, *xs):
        def loss(q, k, v):
            out = fn(q, k, v, kv_mask=kv_mask, causal=causal)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(*xs)
        return (out, *grads)

    flash = lambda *a, **kw: flash_attention(*a, block_q=block_q, block_k=block_k, **kw)
    xs = tuple(x.astype(dtype) for x in (q, k, v))
    before = _kernel_builds()
    unmasked = run(flash, None, *xs)
    assert _built_since(before) == {"unmasked": 1, "masked": 0}
    masked = run(flash, ones, *xs)
    assert _built_since(before) == {"unmasked": 1, "masked": 1}
    ref = run(reference_attention, None, q, k, v)
    f32 = dtype == jnp.float32
    # XLA:CPU contracts `dot * scale - max` into one fused multiply-add where no
    # select stands between them (not causal) and the product is inexact
    # (1/sqrt(128) is no power of two); the chip's compiler does not, and the
    # two variants are equal to the last bit there (PERF.md section 6, PR 34)
    contracted_on_cpu = not causal and D == 128
    for name, a, b, r in zip(("out", "dq", "dk", "dv"), unmasked, masked, ref):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if contracted_on_cpu:
            assert np.max(np.abs(a32 - b32)) <= (4 * float(jnp.finfo(dtype).eps)
                                                 * np.max(np.abs(b32))), name
        elif f32:
            np.testing.assert_array_equal(a32, b32, err_msg=name)
        else:
            # the CPU interpreter may fuse the two bodies differently
            assert _within_one_ulp(a32, b32, dtype), name
        # the file's tolerances: test_flash_matches_reference / _gradients_match
        # (their sum of squares runs over 4x fewer rows) / _bf16_matches_f32_reference
        if name == "out":
            tol = dict(atol=2e-5) if f32 else dict(atol=3e-2)
        else:
            tol = dict(atol=2e-4) if f32 else dict(atol=0.15, rtol=0.05)
        np.testing.assert_allclose(np.asarray(r), np.asarray(a, dtype=np.float32),
                                   err_msg=name, **tol)


@pytest.mark.parametrize("case", ["ragged_T", "padded_keys", "blocks_under_a_lane_tile"])
def test_flash_builds_the_masked_variant_for_a_mask_or_a_ragged_length(case):
    T, block = {"ragged_T": (200, 128), "padded_keys": (256, 128),
                "blocks_under_a_lane_tile": (64, 16)}[case]
    q, k, v, _ = make_qkv(B=2, T=T, H=2, D=64)
    kv_mask = None
    if case == "padded_keys":
        kv_mask = jnp.arange(T)[None, :] < jnp.asarray([T, 100])[:, None]
    before = _kernel_builds()
    out = flash_attention(q, k, v, kv_mask=kv_mask, causal=True, block_q=block, block_k=block)
    assert _built_since(before) == {"unmasked": 0, "masked": 1}
    ref = reference_attention(q, k, v, kv_mask=kv_mask, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_steps_over_the_blocks_that_hold_work_at_8_by_8_blocks(causal):
    q, k, v, _ = make_qkv(B=1, T=1024, H=1, D=64)
    before = _kernel_builds()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    assert _built_since(before) == {"unmasked": 1, "masked": 0}
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)
    assert len(_work_steps(8, 8, 128, 128, causal)[0]) == (36 if causal else 64)


@pytest.mark.parametrize("n_q,n_k,block_q,block_k", [
    (8, 8, 128, 128), (4, 8, 256, 128), (8, 4, 128, 256), (3, 2, 256, 384), (1, 1, 512, 512)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_step_tables_list_every_block_with_a_pair_once_in_row_order(
        causal, n_q, n_k, block_q, block_k):
    """Each query block's key blocks in order from 0, query blocks in order
    (the kernel initialises at key block 0 and writes out at the row's last
    step); under causal exactly the blocks that hold a pair key <= query."""
    want = [(i, j) for i in range(n_q) for j in range(n_k)
            if not causal or j * block_k <= (i + 1) * block_q - 1]
    q_blks, kv_blks = _work_steps(n_q, n_k, block_q, block_k, causal)
    assert q_blks.dtype == kv_blks.dtype == np.int32
    assert list(zip(q_blks.tolist(), kv_blks.tolist())) == want


def test_flash_kernel_builds_count_one_a_trace():
    q, k, v, _ = make_qkv(B=1, T=128, H=1, D=64)
    mask = jnp.ones((1, 128), bool)
    fn = jax.jit(lambda q, k, v, m=None: flash_attention(q, k, v, kv_mask=m))
    before = _kernel_builds()
    fn(q, k, v)
    fn(q, k, v)                                  # the jit cache's entry: no trace
    assert _built_since(before) == {"unmasked": 1, "masked": 0}
    fn(q, k, v, mask)
    fn(q, k, v, mask)
    assert _built_since(before) == {"unmasked": 1, "masked": 1}
    jax.grad(lambda q: jnp.sum(fn(q[:, :64], k[:, :64], v[:, :64])))(q)   # T=64: one block of 64
    assert _built_since(before) == {"unmasked": 1, "masked": 2}


def _two_loop_flash_bwd(causal, block_q, block_k, scale, unmasked, res, g):
    """The backward pass as it was until PR 38, kept as the plain reference of
    the single pass: one loop nest over the pairs for dq, a second one over
    the same pairs for dk and dv, each forming its own ``s``, ``p``, ``dp`` and
    ``ds`` (seven products a pair), the key mask selected on in every call."""
    q, k, v, kv_mask, out, lse = res
    BH, Tq, Dp = q.shape
    Tk, Dvp = k.shape[1], v.shape[2]
    gf = g.astype(q.dtype)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    q_pos, kv_pos = jnp.arange(Tq), jnp.arange(Tk)
    neg = attention._NEG_INF

    def p_block(q_blk, lse_blk, kb_idx, qi0):
        kb = jax.lax.dynamic_slice_in_dim(k, kb_idx * block_k, block_k, axis=1)
        s = jnp.einsum("bqd,bkd->bqk", q_blk, kb, preferred_element_type=jnp.float32) * scale
        mb = jax.lax.dynamic_slice_in_dim(kv_mask, kb_idx * block_k, block_k, axis=1)
        s = jnp.where(mb[:, None, :], s, neg)
        if causal:
            qp = qi0 + q_pos[:block_q][None, :, None]
            kp = kb_idx * block_k + kv_pos[:block_k][None, None, :]
            s = jnp.where(kp <= qp, s, neg)
        return jnp.where(s <= neg * 0.5, 0.0, jnp.exp(s - lse_blk[:, :, None])), kb

    n_qb, n_kb = Tq // block_q, Tk // block_k

    def q_side(qi0):
        return tuple(jax.lax.dynamic_slice_in_dim(x, qi0, block_q, axis=1)
                     for x in (q, lse, gf, delta))

    def dq_one(_, qi):
        qi0 = qi * block_q
        q_blk, lse_blk, g_blk, d_blk = q_side(qi0)

        def inner(ki, dq_acc):
            p, kb = p_block(q_blk, lse_blk, ki, qi0)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * block_k, block_k, axis=1)
            dp = jnp.einsum("bqd,bkd->bqk", g_blk, vb, preferred_element_type=jnp.float32)
            ds = p * (dp - d_blk[:, :, None])
            return dq_acc + jnp.einsum("bqk,bkd->bqd", ds.astype(kb.dtype), kb,
                                       preferred_element_type=jnp.float32) * scale

        last_kb = jnp.minimum(n_kb, (qi0 + block_q - 1) // block_k + 1) if causal else n_kb
        return None, jax.lax.fori_loop(0, last_kb, inner,
                                       jnp.zeros((BH, block_q, Dp), jnp.float32))

    _, dq_blocks = jax.lax.scan(dq_one, None, jnp.arange(n_qb))

    def dkv_one(_, ki):
        ki0 = ki * block_k
        vb = jax.lax.dynamic_slice_in_dim(v, ki0, block_k, axis=1)

        def inner(qi, carry):
            dk_acc, dv_acc = carry
            qi0 = qi * block_q
            q_blk, lse_blk, g_blk, d_blk = q_side(qi0)
            p, _ = p_block(q_blk, lse_blk, ki, qi0)
            dv_acc = dv_acc + jnp.einsum("bqk,bqd->bkd", p.astype(g_blk.dtype), g_blk,
                                         preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqd,bkd->bqk", g_blk, vb, preferred_element_type=jnp.float32)
            ds = p * (dp - d_blk[:, :, None])
            dk_acc = dk_acc + jnp.einsum("bqk,bqd->bkd", ds.astype(q_blk.dtype), q_blk,
                                         preferred_element_type=jnp.float32) * scale
            return dk_acc, dv_acc

        return None, jax.lax.fori_loop(
            ki0 // block_q if causal else 0, n_qb, inner,
            (jnp.zeros((BH, block_k, Dp), jnp.float32),
             jnp.zeros((BH, block_k, Dvp), jnp.float32)))

    _, (dk_blocks, dv_blocks) = jax.lax.scan(dkv_one, None, jnp.arange(n_kb))

    def whole(blocks, T, dtype):
        return jnp.reshape(blocks.transpose(1, 0, 2, 3), (BH, T, blocks.shape[-1])).astype(dtype)

    return (whole(dq_blocks, Tq, q.dtype), whole(dk_blocks, Tk, k.dtype),
            whole(dv_blocks, Tk, v.dtype), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _two_loop_flash_core(q, k, v, kv_mask, causal, block_q, block_k, scale, unmasked):
    return attention._flash_core_fwd_impl(q, k, v, kv_mask, causal, block_q, block_k,
                                          scale, unmasked)[0]


_two_loop_flash_core.defvjp(attention._flash_core_fwd, _two_loop_flash_bwd)


def _grads_of_sum_of_squares(fn, q, k, v, kv_mask, causal):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v, kv_mask=kv_mask, causal=causal) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("D,Dv", [(64, 64), (192, 128)], ids=["64_64", "192_128"])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("keys", ["causal_no_mask", "causal_key_mask", "causal_ragged_T",
                                  "all_pairs_Tq_256_Tk_512"])
def test_flash_backward_in_one_pass_equals_the_two_loop_form(monkeypatch, keys, block_q,
                                                             block_k, D, Dv):
    """dq, dk and dv of the single pass over the tile pairs against the two
    loop nests it replaced, bit for bit in float32 (the same products, every
    sum in the same order), and against ``jax.grad(reference_attention)``."""
    causal = keys != "all_pairs_Tq_256_Tk_512"
    Tq, Tk = {"causal_ragged_T": (300, 300), "all_pairs_Tq_256_Tk_512": (256, 512)}.get(
        keys, (512, 512))
    rs = np.random.default_rng(Tq + block_q + D)
    q = jnp.asarray(rs.normal(size=(2, Tq, 2, D)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(2, Tk, 2, D)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(2, Tk, 2, Dv)), jnp.float32)
    kv_mask = jnp.asarray(rs.random((2, Tk)) > 0.2) if keys == "causal_key_mask" else None
    flash = functools.partial(flash_attention, block_q=block_q, block_k=block_k)
    before = _kernel_builds()
    one_pass = _grads_of_sum_of_squares(flash, q, k, v, kv_mask, causal)
    unmasked = keys in ("causal_no_mask", "all_pairs_Tq_256_Tk_512")
    assert _built_since(before) == {"unmasked": int(unmasked), "masked": int(not unmasked)}
    monkeypatch.setattr(attention, "_flash_core", _two_loop_flash_core)
    two_loops = _grads_of_sum_of_squares(flash, q, k, v, kv_mask, causal)
    ref = _grads_of_sum_of_squares(reference_attention, q, k, v, kv_mask, causal)
    # XLA:CPU contracts `dot * scale - lse` into one fused multiply-add where no
    # select stands between them (no mask, not causal) and the product is
    # inexact (1/sqrt(192) is no power of two); the chip's compiler does not
    contracted_on_cpu = not causal and D == 192
    for name, a, b, r in zip(("dq", "dk", "dv"), one_pass, two_loops, ref):
        assert a.shape == r.shape
        if contracted_on_cpu:
            assert np.max(np.abs(a - b)) <= 4 * np.finfo(np.float32).eps * np.max(np.abs(b)), name
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
        # test_flash_gradients_match's tolerance
        np.testing.assert_allclose(np.asarray(r), np.asarray(a), atol=5e-5, err_msg=name)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for x in value if isinstance(value, (tuple, list)) else (value,):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _eqns_inside_loops(jaxpr, inside=False):
    """Every equation under a ``scan`` or ``while`` of ``jaxpr``, at any depth;
    the forward kernel's own body (``pallas_call``) is not entered."""
    for eqn in jaxpr.eqns:
        if inside:
            yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                yield from _eqns_inside_loops(
                    sub, inside or eqn.primitive.name in ("scan", "while"))


@pytest.mark.parametrize("D,Dv", [(64, 64), (192, 128)], ids=["64_64", "192_128"])
def test_flash_backward_forms_five_products_a_pair_and_no_mask_tile_without_a_mask(
        monkeypatch, D, Dv):
    """What the CPU can count of the backward pass: the loops of an unmasked
    causal call's gradient hold five ``dot_general`` (the two loop nests held
    seven), one ``exp`` and one select a tile (the causal one) and slice no
    boolean; a call with a key mask slices the mask's block and selects on a
    tile twice more (the mask, and the guard of rows with no key left)."""
    T, block = 512, 128
    qk = jnp.zeros((2, T, 2, D), jnp.float32)
    v = jnp.zeros((2, T, 2, Dv), jnp.float32)

    flash = functools.partial(flash_attention, block_q=block, block_k=block)

    def loop_eqns(core, kv_mask):
        with monkeypatch.context() as m:
            m.setattr(attention, "_flash_core", core)
            jaxpr = jax.make_jaxpr(lambda q, k, v: _grads_of_sum_of_squares(
                flash, q, k, v, kv_mask, True))(qk, qk, v)
        return list(_eqns_inside_loops(jaxpr.jaxpr))

    def count(eqns, primitive):
        return sum(e.primitive.name == primitive for e in eqns)

    def mask_blocks(eqns):
        return [e for e in eqns if e.primitive.name == "dynamic_slice"
                and e.invars[0].aval.dtype == jnp.bool_]

    unmasked = loop_eqns(attention._flash_core, None)
    masked = loop_eqns(attention._flash_core, jnp.ones((2, T), bool))
    assert count(unmasked, "dot_general") == count(masked, "dot_general") == 5
    assert count(loop_eqns(_two_loop_flash_core, None), "dot_general") == 7
    assert mask_blocks(unmasked) == []
    assert [e.outvars[0].aval.shape for e in mask_blocks(masked)] == [(4, block)]
    for eqns, selects in ((unmasked, 1), (masked, 3)):
        tiles = [e.primitive.name for e in eqns if e.outvars[0].aval.shape == (4, block, block)]
        assert tiles.count("select_n") == selects and tiles.count("exp") == 1


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    q, k, v, mask = make_qkv()
    mesh = create_mesh(MeshConfig(data=1, seq=8))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=causal)
    out = ring_attention_sharded(mesh, q, k, v, kv_mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_ring_attention_bf16_matches_f32_reference():
    # MXU training path: bf16 shards, ring einsums in bf16 with f32
    # accumulation and f32 softmax statistics/traveling grad accumulators
    q, k, v, mask = make_qkv(T=32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mesh = create_mesh(MeshConfig(data=1, seq=8))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=True)
    out = ring_attention_sharded(mesh, qb, kb, vb, kv_mask=mask, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ref),
                               np.asarray(out, dtype=np.float32), atol=3e-2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(
            mesh, q, k, v, kv_mask=mask, causal=True).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, kv_mask=mask,
                                           causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(qb, kb, vb)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(b, dtype=np.float32),
                                   atol=0.15, rtol=0.05)


def test_ring_attention_mixed_mesh():
    # data×seq mesh: batch and sequence sharded simultaneously
    q, k, v, mask = make_qkv(B=4, T=32)
    mesh = create_mesh(MeshConfig(data=2, seq=4))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=True)
    out = ring_attention_sharded(mesh, q, k, v, kv_mask=mask, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_ring_attention_differentiable():
    q, k, v, _ = make_qkv(T=32)
    mesh = create_mesh(MeshConfig(data=1, seq=8))

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(mesh, q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_encoder_attn_impls_agree():
    """The same Encoder weights produce the same output under einsum, flash,
    ring, and ulysses (on a seq mesh) attention backends (valid positions
    only)."""
    import dataclasses

    from synapseml_tpu.models.flax_nets.transformer import Encoder, TransformerConfig

    base = TransformerConfig(hidden=32, n_layers=2, n_heads=4, mlp_dim=64,
                             max_len=32, dtype=jnp.float32, causal=True)
    B, T = 2, 32
    rs = np.random.default_rng(0)
    x = jnp.asarray(rs.normal(size=(B, T, base.hidden)), jnp.float32)
    mask_1d = np.ones((B, T), bool)
    mask_1d[:, -5:] = False
    mask = jnp.asarray(mask_1d)[:, None, None, :]

    enc = Encoder(base)
    variables = enc.init(jax.random.PRNGKey(0), x, mask)

    out_einsum = enc.apply(variables, x, mask)
    out_flash = Encoder(dataclasses.replace(base, attn_impl="flash")).apply(variables, x, mask)
    valid = np.asarray(mask_1d)
    np.testing.assert_allclose(np.asarray(out_einsum)[valid],
                               np.asarray(out_flash)[valid], atol=2e-4)

    mesh = create_mesh(MeshConfig(data=2, seq=4))
    with mesh.scope():
        out_ring = Encoder(dataclasses.replace(base, attn_impl="ring")).apply(variables, x, mask)
    np.testing.assert_allclose(np.asarray(out_einsum)[valid],
                               np.asarray(out_ring)[valid], atol=2e-4)

    with mesh.scope():  # n_heads=4 divides seq=4: ulysses eligible
        out_uly = Encoder(dataclasses.replace(base, attn_impl="ulysses")).apply(variables, x, mask)
    np.testing.assert_allclose(np.asarray(out_einsum)[valid],
                               np.asarray(out_uly)[valid], atol=2e-4)


def test_ring_attention_grad_matches_reference_with_mask():
    """Custom-VJP gradients == autodiff through reference_attention, with
    padding mask + causal + chunked inner (chunk < T_local)."""
    mesh = create_mesh(MeshConfig(seq=4))
    rs = np.random.default_rng(7)
    B, T, H, D = 2, 64, 2, 16
    q = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.float32)
    mask = np.ones((B, T), bool)
    mask[1, 50:] = False
    mask = jnp.asarray(mask)
    w = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.float32)  # cotangent mix

    def loss_ring(q, k, v):
        out = ring_attention_sharded(mesh, q, k, v, kv_mask=mask, causal=True,
                                     chunk=8)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, kv_mask=mask, causal=True) * w)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_ring_attention_long_context_32k():
    """T=32k over an 8-way seq mesh: rolled ring + chunked inner must compile
    (compile size independent of ring length) and run without a [T_loc, T_loc]
    score materialization. reference check on a strided sample of rows."""
    mesh = create_mesh(MeshConfig(seq=8))
    rs = np.random.default_rng(11)
    B, T, H, D = 1, 32768, 1, 64
    q = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.bfloat16)
    k = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.bfloat16)
    v = jnp.asarray(rs.normal(size=(B, T, H, D)), jnp.bfloat16)
    out = np.asarray(ring_attention_sharded(mesh, q, k, v, causal=True,
                                            chunk=1024))
    assert out.shape == (B, T, H, D)
    assert np.all(np.isfinite(out))
    # spot-check rows against local attention over their causal prefix
    qf, kf, vf = (np.asarray(x, np.float32) for x in (q, k, v))
    for t in (0, 5000, 20000, 32767):
        s = (qf[0, t, 0] @ kf[0, : t + 1, 0].T) / np.sqrt(D)
        p = np.exp(s - s.max())
        p /= p.sum()
        np.testing.assert_allclose(out[0, t, 0], p @ vf[0, : t + 1, 0],
                                   atol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(causal):
    from synapseml_tpu.ops import ulysses_attention_sharded

    q, k, v, mask = make_qkv(H=8)  # ulysses: heads divisible by seq size
    mesh = create_mesh(MeshConfig(data=1, seq=8))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=causal)
    out = ulysses_attention_sharded(mesh, q, k, v, kv_mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_ulysses_attention_mixed_mesh_and_grad():
    """data x seq mesh; gradients flow through both all-to-alls correctly."""
    from synapseml_tpu.ops import ulysses_attention_sharded

    q, k, v, mask = make_qkv(B=4, T=32)
    mesh = create_mesh(MeshConfig(data=2, seq=4))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=True)
    out = ulysses_attention_sharded(mesh, q, k, v, kv_mask=mask, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def loss_u(q, k, v):
        return jnp.sum(ulysses_attention_sharded(mesh, q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_u = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("strategy", ["ulysses", "ring"])
def test_seq_parallel_with_tensor_parallel_heads(strategy):
    """seq AND tensor axes together (VERDICT r3 weak-6: the head_axis x TP
    interaction was untested beyond the divisibility guard). H=8 over
    tensor=2 engages head sharding — for Ulysses the divisor is
    tensor*seq=4 (heads split across the seq axis by the all-to-all too);
    outputs and grads must match unsharded reference attention."""
    from synapseml_tpu.ops import ring_attention_sharded, ulysses_attention_sharded

    fn = (ulysses_attention_sharded if strategy == "ulysses"
          else ring_attention_sharded)
    q, k, v, mask = make_qkv(B=2, T=32, H=8)
    mesh = create_mesh(MeshConfig(data=2, seq=2, tensor=2))
    for causal in (False, True):
        ref = reference_attention(q, k, v, kv_mask=mask, causal=causal)
        out = fn(mesh, q, k, v, kv_mask=mask, causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def loss_s(q, k, v):
        return jnp.sum(fn(mesh, q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_s = jax.grad(loss_s, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_ulysses_head_axis_disengages_when_indivisible():
    """H=6 divides the seq size (3 heads per shard after the all-to-all) but
    not tensor*seq=4, so the head PartitionSpec must silently drop the
    tensor axis rather than produce a wrong sharding — output still exact."""
    from synapseml_tpu.ops import ulysses_attention_sharded

    q, k, v, mask = make_qkv(B=2, T=32, H=6)
    mesh = create_mesh(MeshConfig(data=2, seq=2, tensor=2))
    ref = reference_attention(q, k, v, kv_mask=mask, causal=True)
    out = ulysses_attention_sharded(mesh, q, k, v, kv_mask=mask, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from synapseml_tpu.ops.ulysses_attention import ulysses_attention

    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(jnp.zeros((1, 4, 6, 8)), jnp.zeros((1, 4, 6, 8)),
                          jnp.zeros((1, 4, 6, 8)), axis_name="seq",
                          axis_size=4)
