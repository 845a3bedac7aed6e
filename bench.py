"""Benchmark rotation: one child process per config, strictly one after
another, from a parent that never imports jax — a chip belongs to one
process at a time.

Prints one JSON line per config (the BERT-base flagship last) and exits
non-zero when any config fails, outruns its deadline, or finds no
accelerator. Every line names ``platform``, ``device_kind`` and the device
count. A CPU run happens only when ``JAX_PLATFORMS=cpu`` is given (smoke
sizes; every line then says ``cpu`` and its numbers are not device
metrics). ``BENCH_CONFIGS=a,b`` restricts the rotation.

What the flagship measures (K optimizer steps inside one lax.scan dispatch,
``6*N*B*T`` model FLOPs, a dispatch+fetch latency subtracted) is unchanged
here; replacing it with a cell benchmark is ROADMAP A1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# (name, benchmarks/ module or None for the in-file flagship, deadline
# seconds, tpu_only). A tpu_only config asks a question about the MXU and
# has no CPU smoke size: under JAX_PLATFORMS=cpu it prints a skipped line.
CONFIGS = [
    ("onnx-resnet", "onnx_resnet50", 300, False),
    # llama-decode also carries the continuous_ab record: run-to-completion
    # generate vs paged continuous decode on a mixed-length stream
    ("llama-decode", "llama_decode", 300, False),
    ("gbdt-higgs", "gbdt_higgs1m", 420, False),
    ("gbdt-hist-backends", "gbdt_hist_backends", 420, True),
    ("attn-backends", "attn_backends", 600, True),  # 4 BERT-base scan compiles
    # host-side serving A/B (adaptive continuous batching vs fixed-timeout)
    ("serving-microbatch", "serving_microbatch", 240, False),
    # streamed fit_source vs eager fit_arrays over a multi-shard jsonl dataset
    ("data-pipeline", "data_pipeline", 240, False),
    # HPO sweep A/B: serial TuneHyperparameters vs ONE fused training array
    ("hpo-fused", "hpo_fused", 300, False),
    # bulk-scoring A/B: in-memory transform vs streamed transform_source
    ("bulk-scoring", "bulk_scoring", 240, False),
    # deploy cold-start A/B: AOT executable ladder vs JIT warmup; its arms
    # are fresh subprocesses that name JAX_PLATFORMS=cpu themselves
    ("deploy-coldstart", "deploy_coldstart", 420, False),
    # replicated vs ZeRO-sharded weight update; arms are fresh 4-device CPU
    # subprocesses
    ("sharded-train", "sharded_train", 300, False),
    # static vs autoscaled subprocess fleets under a step load (CPU workers)
    ("fleet-elastic", "fleet_elastic", 360, False),
    # 2-worker shard fan-out vs in-process brute force (CPU workers)
    ("retrieval-serve", "retrieval_serve", 300, False),
    # fused perturbation scoring vs serial per-row transform
    ("explain-bulk", "explain_bulk", 240, False),
    ("flagship", None, 420, False),
    ("vit", "vit_finetune", 450, False),
]


# --------------------------------------------------------------------------
# child: the measurement (one process per config; it alone touches jax)
# --------------------------------------------------------------------------

def _timed_scan(trainer, state, batch, k):
    import jax

    stacked = jax.tree.map(lambda x: np.broadcast_to(x, (k,) + x.shape).copy(), batch)
    t0 = time.perf_counter()
    new_state, metrics = trainer.train_steps_scan(state, stacked)
    losses = np.asarray(metrics["loss"])  # value fetch = real sync
    if not np.all(np.isfinite(losses)) or np.count_nonzero(losses) == 0:
        raise RuntimeError(f"scan returned degenerate losses: {losses[:4]}...")
    return time.perf_counter() - t0, new_state, float(losses[-1])


def _roundtrip_latency(n_trials: int = 5) -> float:
    """Fixed dispatch+fetch latency of a trivial program on the same path."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    float(f(x))  # compile
    ts = []
    for _ in range(n_trials):
        t0 = time.perf_counter()
        float(f(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_bench(devices):
    import jax

    from synapseml_tpu.core.instrumentation import chip_peak_tflops
    from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_base, bert_tiny
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        cfg = bert_base()          # 110M params, the reference DeepTextClassifier default
        B, T = 32, 128             # reference max_token_len default = 128
        k = 48
    else:                          # JAX_PLATFORMS=cpu smoke sizes
        cfg = bert_tiny()
        B, T = 16, 32
        k = 8

    model = BertClassifier(cfg, num_classes=2)
    mesh = create_mesh(MeshConfig(data=-1))
    trainer = Trainer(model, mesh, TrainerConfig(learning_rate=5e-5, total_steps=10_000))

    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32),
        "attention_mask": np.ones((B, T), np.int32),
        "labels": rng.integers(0, 2, (B,)).astype(np.int32),
    }
    state = trainer.init_state(batch)

    from synapseml_tpu.core.observability import get_registry

    _, state, _ = _timed_scan(trainer, state, batch, k)  # compile + warm
    overhead = _roundtrip_latency()
    trials = []
    loss = float("nan")
    step_hist = get_registry().histogram(
        "synapseml_train_step_duration_ms",
        "training step (boosting iteration / optimizer step) wall time",
        ("engine",)).labels(engine="flagship")
    for _ in range(3):
        t, state, loss = _timed_scan(trainer, state, batch, k)
        trials.append(t)
        step_hist.observe(max(t - overhead, 0.0) / k * 1e3)
    step_s = max((min(trials) - overhead) / k, 1e-9)
    n_chips = jax.device_count()
    samples_per_sec_chip = B / step_s / n_chips

    # model FLOPs estimate: 6 * params * tokens per fwd+bwd
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(state.params))
    tflops = 6 * n_params * B * T / step_s / 1e12

    result = {
        "metric": "DeepTextClassifier BERT-base fine-tune throughput"
                  if on_tpu else "DeepTextClassifier bert-tiny (CPU smoke)",
        "value": round(samples_per_sec_chip, 2),
        "unit": "samples/sec/chip",
        "platform": platform,
        "batch": B,
        "seq_len": T,
        "step_ms": round(step_s * 1e3, 2),
        "model_tflops_per_sec": round(tflops, 1),
        "final_loss": round(loss, 4),
    }
    if on_tpu:
        peak = chip_peak_tflops(devices[0].device_kind)
        result["mfu"] = round(tflops / n_chips / peak, 4)
        get_registry().gauge(
            "synapseml_train_mfu",
            "model FLOPs utilization vs chip_peak_tflops", ("engine",),
        ).set(result["mfu"], engine="flagship")
    return result


def _child_main(config: str) -> None:
    """Bring up the backend, refuse a CPU nobody asked for, measure, print
    the result line."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from benchmarks._common import init_jax

    jax, plat, n_chips = init_jax()
    devices = jax.devices()
    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if plat == "cpu" and not cpu_asked:
        sys.exit("bench: JAX found no accelerator (platform 'cpu'); set "
                 "JAX_PLATFORMS=cpu to ask for the CPU smoke sizes")
    module = {name: mod for name, mod, _, _ in CONFIGS}[config]
    if module is None:
        result = run_bench(devices)
    else:
        import importlib

        result = importlib.import_module(module).run(jax, plat, n_chips)
    result.update(platform=plat, device_kind=devices[0].device_kind,
                  n_devices=len(devices))
    # every record carries the child's MetricsRegistry snapshot so the
    # perf trajectory keeps full histograms (p50/p95/p99), not just means
    from synapseml_tpu.core.observability import get_registry

    result["metrics"] = get_registry().snapshot()
    print("BENCH_RESULT " + json.dumps(result), flush=True)


# --------------------------------------------------------------------------
# parent: orchestration (never imports jax)
# --------------------------------------------------------------------------

def _run_child(config: str, timeout_s: float):
    """(result dict or None, failure reason or None) for one config's child."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", config],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        return None, f"exceeded {timeout_s:.0f}s; last output: " \
            + " | ".join(out.splitlines()[-3:])[-400:]
    for line in out.splitlines():
        if line.startswith("BENCH_RESULT "):
            return json.loads(line[len("BENCH_RESULT "):]), None
    return None, f"rc={proc.returncode}: " \
        + " | ".join(out.splitlines()[-6:])[-600:]


def main() -> int:
    if "--child" in sys.argv:
        _child_main(sys.argv[sys.argv.index("--child") + 1])
        return 0

    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    only = {c.strip() for c in os.environ.get("BENCH_CONFIGS", "").split(",")
            if c.strip()}
    configs = [c for c in CONFIGS if not only or c[0] in only]

    lines: list = []  # (name, record) in config order; flagship printed last
    failures = 0
    for name, _module, timeout_s, tpu_only in configs:
        if tpu_only and cpu_asked:
            lines.append((name, {"metric": name, "platform": "cpu",
                                 "skipped": "tpu-only config"}))
            continue
        t0 = time.monotonic()
        result, err = _run_child(name, timeout_s)
        if result is None:
            failures += 1
            print(f"# {name} FAILED after {time.monotonic() - t0:.0f}s: {err}",
                  file=sys.stderr, flush=True)
            result = {"metric": name, "failed": err}
        lines.append((name, result))

    for name, result in sorted(lines, key=lambda nr: nr[0] == "flagship"):
        print(json.dumps(result), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
