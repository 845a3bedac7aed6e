"""What a CPU can check about the chip paths: every Pallas kernel lowers for
TPU in COMPILED mode (cross-lowering — it cannot catch a Mosaic compile
refusal, ``chip_smoke.py`` Leg B does), and nothing hides the device: worker
mains have no CPU default, launchers refuse what can only hang, the compile
cache sits where it was told, unknown hardware is an error."""

import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from synapseml_tpu.core import observability as obs
from synapseml_tpu.core import platform

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# cross-lowering for TPU
# ---------------------------------------------------------------------------

@pytest.fixture
def compiled_pallas(monkeypatch):
    """Force the one interpret decision to 'compiled' (what a TPU gets)."""
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)


def _tpu_module(fn, *specs) -> str:
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs).mlir_module()


def test_lowering_tests_cover_every_pallas_call():
    sites = {str(p.relative_to(REPO)) for p in (REPO / "synapseml_tpu").rglob("*.py")
             if re.search(r"\bpl\.pallas_call\(", p.read_text())}
    assert sites == {"synapseml_tpu/ops/attention.py",
                     "synapseml_tpu/gbdt/pallas_hist.py"}, (
        f"a pallas_call appeared or moved ({sorted(sites)}): add it to the "
        "cross-lowering tests below and to chip_smoke.py Leg B")


def _cell_flash_grad(q, k, v, *mask):
    """dq, dk, dv of a flash cell's call: causal, blocks of 512."""
    from synapseml_tpu.ops import flash_attention

    return jax.grad(lambda q_, k_, v_: jnp.sum(flash_attention(
        q_, k_, v_, *mask, causal=True, block_q=512, block_k=512).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


def _assert_one_pass_backward(text: str, dq_blocks: str, masked: bool):
    """The kernel is opaque in the module, so every ``dot_general`` is the
    backward's: five a tile pair in one loop nest, ``dq`` accumulated in a
    float32 buffer of query blocks, and a block of the key mask sliced only
    where the call passed one."""
    assert text.count("stablehlo.dot_general") == 5
    assert dq_blocks in text
    assert ("tensor<32x512xi1>" in text) == masked


@pytest.mark.parametrize("B,T,H,D,causal", [
    (8, 512, 12, 64, False),    # chip_smoke Leg B's shape
    (2, 100, 4, 64, True),      # ragged T, causal
])
def test_flash_attention_lowers_for_tpu(compiled_pallas, B, T, H, D, causal):
    from synapseml_tpu.ops import flash_attention

    qkv = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((B, T), jnp.bool_)

    def fwd(q, k, v, m):
        return flash_attention(q, k, v, kv_mask=m, causal=causal)

    def grad(q, k, v, m):
        return jax.grad(lambda q_, k_, v_: jnp.sum(
            fwd(q_, k_, v_, m).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)

    for fn in (fwd, grad):
        assert "tpu_custom_call" in _tpu_module(fn, qkv, qkv, qkv, mask)


@pytest.mark.parametrize("variant", ["unmasked", "masked"])
def test_flash_attention_lowers_for_tpu_at_the_long_context_cells_shape(compiled_pallas, variant):
    """`lfm2_24b_a2b_ep8.lm_32k`: one 32,768-token row, 32 heads of 64 (padded
    to the 128 lanes), causal, blocks of 512, forward and gradient. The cell
    passes no mask and builds the unmasked kernel; a padding mask at the same
    shape builds the masked one."""
    qkv = jax.ShapeDtypeStruct((1, 32768, 32, 64), jnp.bfloat16)
    mask = [jax.ShapeDtypeStruct((1, 32768), jnp.bool_)] if variant == "masked" else []
    series = 'synapseml_flash_kernel_builds_total{variant="%s"}' % variant
    before = obs.get_registry().snapshot().get(series, 0.0)
    text = _tpu_module(_cell_flash_grad, qkv, qkv, qkv, *mask)
    assert "tpu_custom_call" in text and "32768x128" in text
    assert obs.get_registry().snapshot()[series] == before + 1
    _assert_one_pass_backward(text, "64x32x512x128xf32", masked=variant == "masked")


@pytest.mark.parametrize("variant", ["unmasked", "masked"])
def test_flash_attention_lowers_for_tpu_at_the_latent_cells_widths(compiled_pallas, variant):
    """`moonlight_16b_a3b_ep8.lm_8k_latent`: two 8,192-token rows, 16 heads,
    queries and keys 192 wide (256 lanes) beside values 128 wide (128 lanes),
    causal, blocks of 512, forward and gradient: the kernel's output is as wide
    as the values."""
    qk = jax.ShapeDtypeStruct((2, 8192, 16, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 8192, 16, 128), jnp.bfloat16)
    mask = [jax.ShapeDtypeStruct((2, 8192), jnp.bool_)] if variant == "masked" else []
    text = _tpu_module(_cell_flash_grad, qk, qk, v, *mask)
    assert "tpu_custom_call" in text and "8192x256" in text and "8192x128" in text
    _assert_one_pass_backward(text, "16x32x512x256xf32", masked=variant == "masked")


@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a v5e that is described, not attached: the TPU's own
    compiler runs here (Mosaic for the kernel, XLA:TPU for the backward's
    loops) and says what the program asks of the chip's memory."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("qk_shape,v_shape,temp_gb", [
    pytest.param((1, 32768, 32, 64), (1, 32768, 32, 64), 2.3, id="lfm2_24b_a2b_ep8.lm_32k"),
    pytest.param((2, 8192, 16, 192), (2, 8192, 16, 128), 0.75,
                 id="moonlight_16b_a3b_ep8.lm_8k_latent"),
])
def test_flash_gradient_compiles_for_a_v5e_at_the_cells_shapes(
        compiled_pallas, one_v5e, qk_shape, v_shape, temp_gb):
    """The op's gradient as the two flash cells run it (no mask, causal, blocks
    of 512) through the chip's compiler. Its temporaries hold the backward's
    float32 ``dq`` buffer (537 MB and 268 MB) beside ``dk`` and ``dv``: 2.15
    and 0.67 GB as this was written."""
    from jax.experimental.compilation_cache import compilation_cache

    qk = jax.ShapeDtypeStruct(qk_shape, jnp.bfloat16, sharding=one_v5e)
    v = jax.ShapeDtypeStruct(v_shape, jnp.bfloat16, sharding=one_v5e)
    # an executable for a described chip cannot be read back from the cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(_cell_flash_grad).lower(qk, qk, v).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9


def test_windowed_flash_gradient_compiles_for_a_v5e_at_the_window_cells_shape(
        compiled_pallas, one_v5e):
    """The op's gradient as the window cell's sliding layers run it (one row of
    8,192 positions, 64 heads of 128, a window of 512, blocks of 512: the
    unmasked kernel over the band's tiles, the backward's loops stopped at the
    band) through the chip's compiler, with the kernel under `attn.window`."""
    from jax.experimental.compilation_cache import compilation_cache

    from synapseml_tpu.ops import flash_attention

    qkv = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16, sharding=one_v5e)

    def grad(q, k, v):
        return jax.grad(lambda q_, k_, v_: jnp.sum(flash_attention(
            q_, k_, v_, causal=True, block_q=512, block_k=512, window=512).astype(
                jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = jax.jit(grad).lower(qkv, qkv, qkv)
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    text = lowered.as_text(debug_info=True)
    assert "attn.window" in text and "attn.flash" not in text
    # the float32 dq buffer of the backward (268 MB) beside dk and dv, as for the causal op
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("N,WB", [
    (1_000_000, 32 * 256),      # chip_smoke Leg B's shape (Higgs-1M, depth-5 level)
    (1001, 300),                # ragged rows and bins
])
def test_histogram_kernel_lowers_for_tpu(compiled_pallas, N, WB):
    from synapseml_tpu.gbdt.pallas_hist import pallas_segment_histogram

    # the undecorated function: the jitted one may hold an interpret-mode
    # trace of the same shapes from another test
    text = _tpu_module(
        lambda s, d: pallas_segment_histogram.__wrapped__(s, d, WB),
        jax.ShapeDtypeStruct((N,), jnp.int32),
        jax.ShapeDtypeStruct((N, 3), jnp.float32))
    assert "tpu_custom_call" in text


def test_pallas_interpret_only_on_cpu(monkeypatch):
    assert platform.pallas_interpret() is True          # the tests' backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="compiled on 'tpu'"):
        platform.pallas_interpret()


# ---------------------------------------------------------------------------
# nothing hides the device
# ---------------------------------------------------------------------------

def test_worker_mains_do_not_force_cpu(tmp_path):
    """A worker main started with NO JAX_PLATFORMS in its environment leaves
    the platform choice to jax (and turns the compile cache on at the fixed
    in-checkout path)."""
    code = """
import jax
import synapseml_tpu.io.serving as serving, synapseml_tpu.fleet.residency as residency
import synapseml_tpu.hf as hf
from synapseml_tpu.io.distributed_serving import llm_worker_main, worker_main
from synapseml_tpu.fleet.autoscaler import fleet_worker_main
from synapseml_tpu.retrieval.serve import retrieval_worker_main

class Stop(Exception):
    pass
def stop(*a, **k):
    raise Stop
serving.serve_pipeline = serving.serve_llm = residency.serve_multi_model = stop
hf.HuggingFaceCausalLM = lambda **k: None
open(PKL, "wb").write(__import__("pickle").dumps(None))
for main, args in [(worker_main, (PKL, "http://x")), (llm_worker_main, ("llama-tiny", "http://x")),
                   (fleet_worker_main, (ROOT, "m")), (retrieval_worker_main, (ROOT, "i"))]:
    try:
        main(*args)
    except Stop:
        pass
    else:
        raise SystemExit(f"{main.__name__} did not reach its server")
    print(main.__name__, jax.config.jax_platforms, jax.config.jax_compilation_cache_dir)
""".replace("PKL", repr(str(tmp_path / "p.pkl"))).replace("ROOT", repr(str(tmp_path)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = str(REPO)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 4, r.stdout
    for line in lines:
        _name, platforms, cache_dir = line.split()
        assert platforms == "None", line            # not forced to anything
        assert cache_dir == str(REPO / ".jax_cache"), line


def test_compile_cache_helper(monkeypatch):
    # an outside placement wins and the helper sets NO directory in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    assert platform.enable_compile_cache() == "/x"
    assert calls == []
    # otherwise: the fixed in-checkout path, derived from the package location
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert platform.enable_compile_cache() == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]


def test_chip_launch_refusals(monkeypatch):
    monkeypatch.setattr(platform, "_visible_tpu_chips", lambda: 4)
    monkeypatch.setattr(platform, "_parent_holds_tpu", lambda: False)
    platform.check_chip_launch(8, {"JAX_PLATFORMS": "cpu"})     # explicit CPU
    platform.check_chip_launch(1, {})                           # one chip holder
    with pytest.raises(RuntimeError, match="2 chip-holding worker"):
        platform.check_chip_launch(2, {})
    monkeypatch.setattr(platform, "_parent_holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="holds the chip"):
        platform.check_chip_launch(1, {"JAX_PLATFORMS": "tpu"})
    # no chip on the host and none held: nothing to refuse
    monkeypatch.setattr(platform, "_visible_tpu_chips", lambda: 0)
    monkeypatch.setattr(platform, "_parent_holds_tpu", lambda: False)
    platform.check_chip_launch(8, {})


def test_unknown_tpu_has_no_peak():
    from synapseml_tpu.core.instrumentation import chip_peak_tflops

    assert chip_peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(ValueError, match="no bf16 peak recorded"):
        chip_peak_tflops("TPU v99")


def test_sequence_parallel_attention_needs_its_mesh():
    """ring/ulysses with no seq axis in scope is an error, not a quiet swap
    to a local kernel."""
    from synapseml_tpu.models.flax_nets.transformer import Encoder, TransformerConfig

    cfg = TransformerConfig(hidden=16, n_layers=1, n_heads=2, mlp_dim=32,
                            max_len=8, dtype=jnp.float32, attn_impl="ring")
    x = jnp.zeros((1, 8, 16))
    variables = Encoder(cfg).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="needs a mesh"):
        Encoder(cfg).apply(variables, x)


def test_create_mesh_logs_when_it_degrades(caplog):
    from synapseml_tpu.parallel import MeshConfig, create_mesh

    with caplog.at_level("WARNING", logger="synapseml_tpu.parallel.mesh"):
        mesh = create_mesh(MeshConfig(data=1, fsdp=3))           # 8 devices
    assert mesh.axis_sizes["data"] == 8
    assert "degrading to pure data parallel" in caplog.text
    with pytest.raises(ValueError):
        create_mesh(MeshConfig(data=1, fsdp=3), allow_fewer=False)


def test_chip_smoke_refuses_a_cpu():
    """No accelerator and no explicit small mode: non-zero exit before any
    work, and no result line."""
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr
