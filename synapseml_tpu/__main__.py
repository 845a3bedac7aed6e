"""``python -m synapseml_tpu`` — environment self-test.

Answers "does this install work on this machine" in under a minute: backend
and mesh detection, a GBDT fit/score, a text-classifier train step, an
ONNX conversion round trip, and the native library build — each reported
PASS/FAIL with the failure captured instead of a stack-trace bail (mirrors
the role of the reference's notebook smoke tier for cluster validation).

It runs on the accelerator JAX finds and FAILS when there is none; ``--cpu``
is the explicit way to test a CPU-only install. (``python chip_smoke.py`` at
the repo root is the full-width proof on a TPU.)
"""

from __future__ import annotations

import sys
import time


def _check(name: str, fn, report: list) -> None:
    t0 = time.perf_counter()
    try:
        detail = fn() or ""
        report.append((name, True, f"{time.perf_counter() - t0:.1f}s", detail))
    except Exception as e:  # noqa: BLE001 — the point is the report
        report.append((name, False, f"{time.perf_counter() - t0:.1f}s",
                       f"{type(e).__name__}: {e}"))


def selftest(argv: list[str] | None = None) -> int:
    import numpy as np

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--help" in argv or "-h" in argv:
        print("usage: synapseml-tpu-selftest [--cpu]\n\n"
              "Environment self-test: backend, mesh, GBDT, text classifier,\n"
              "ONNX registry, native build — each reported PASS/FAIL.\n\n"
              "  --cpu   run on the CPU (without it, finding no "
              "accelerator is a failure)")
        return 0

    import jax

    from .core.platform import enable_compile_cache

    cpu = "--cpu" in argv
    if cpu:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()

    report: list = []

    def backend():
        devs = jax.devices()
        if devs[0].platform == "cpu" and not cpu:
            raise RuntimeError(
                "JAX found no accelerator (platform 'cpu'); pass --cpu to "
                "test a CPU-only install")
        return f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"

    def mesh():
        from .parallel import MeshConfig, create_mesh

        m = create_mesh(MeshConfig(data=-1))
        return f"axes={m.axis_sizes}"

    def gbdt():
        from .core import DataFrame
        from .gbdt import LightGBMClassifier

        rs = np.random.default_rng(0)
        X = rs.normal(size=(400, 6)).astype(np.float32)
        y = (X @ rs.normal(size=6) > 0).astype(np.int32)
        df = DataFrame.from_dict({"features": X, "label": y})
        model = LightGBMClassifier(num_iterations=5, num_leaves=7,
                                   max_bin=63).fit(df)
        acc = float(np.mean(model.transform(df).collect_column("prediction") == y))
        assert acc > 0.7, f"accuracy {acc}"
        return f"train acc {acc:.2f}"

    def text():
        from .core import DataFrame
        from .models import DeepTextClassifier

        df = DataFrame.from_rows([{"text": "good great", "label": 1},
                                  {"text": "bad awful", "label": 0}] * 8)
        model = DeepTextClassifier(checkpoint="bert-tiny", num_classes=2,
                                   batch_size=8, max_token_len=16,
                                   max_steps=4, learning_rate=3e-3).fit(df)
        out = model.transform(df)
        return f"{out.count()} rows scored"

    def onnx():
        from .onnx import ONNXModel
        from .onnx.convert import OP_REGISTRY

        assert len(OP_REGISTRY) > 130
        return f"{len(OP_REGISTRY)} ops registered"

    def native():
        from . import native as nat

        if not nat.available():
            raise RuntimeError(f"build failed ({nat.build_error()}); the "
                               "pure-python fallback is in use")
        return "built"

    _check("jax backend", backend, report)
    _check("device mesh", mesh, report)
    _check("gbdt fit/score", gbdt, report)
    _check("text classifier", text, report)
    _check("onnx registry", onnx, report)
    _check("native library", native, report)

    width = max(len(n) for n, *_ in report)
    failures = 0
    for name, ok, took, detail in report:
        status = "PASS" if ok else "FAIL"
        failures += 0 if ok else 1
        print(f"{name.ljust(width)}  {status}  {took:>6}  {detail}")
    print(f"{'-' * (width + 20)}\n"
          f"{len(report) - failures}/{len(report)} checks passed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(selftest(sys.argv[1:]))
