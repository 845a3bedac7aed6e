"""Deploy cold-start A/B: publish-once AOT executable ladders vs JIT warmup.

The ISSUE-9 acceptance measurement: publish ONE artifact (a deep ONNX MLP
pipeline with its full bucket ladder AOT-compiled + serialized into the
registry at publish time), then hot-swap it onto a fresh worker process
twice in the SAME round:

  (a) aot — ``/admin/load`` maps in the precompiled executables (the
      manifest's full-ladder warmup replays; the PR-4 "rungs <= 64"
      default cap is lifted because loading an executable is I/O);
  (b) jit — the same artifact with ``"aot": false`` (identical bytes,
      identical numerics), paying jit traces at warmup under the default
      small-rung cap, exactly like every pre-ISSUE-9 rollout.

Each arm is a FRESH subprocess (cold process-level caches — the honest
cold-start). Reported per arm: total swap wall (``load_ms``), the warmup
breakdown (io_ms / compile_ms / executables loaded vs traced), the first
post-swap HTTP request, and the FIRST RUNG-128 BATCH: 96 rows pushed
through the exact serve-loop batch preparation (``run_warmup`` — what the
adaptive scheduler hands the pipeline when a post-cutover burst drains),
a rung the JIT arm's capped warmup never compiled, so its first big batch
pays the compile the AOT arm shipped from publish. (A threaded HTTP burst
measures GIL contention on a small host, not the compile stall — the
direct serve-loop form is the low-noise measurement of the same event.)
Gates: byte-identical predictions between arms, zero traced executables
in the AOT arm, and AOT first-128-batch wall <= 0.5x the JIT arm's.

All measurement subprocesses name ``JAX_PLATFORMS=cpu`` so publish and
load fingerprints match regardless of the parent's backend (a TPU A/B
needs the grandchildren to own the chip, one at a time). Prints one JSON
line.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent))

BUCKETS = [8, 16, 32, 64, 128]
DIN, DOUT, WIDTH, DEPTH = 16, 4, 256, 12
FIRST_BATCH = 96  # pads to rung 128 — past the default JIT warmup cap


# ---------------------------------------------------------------------------
# the published pipeline (module-level: grandchildren import by name, so
# the serialized class path 'deploy_coldstart.*' resolves everywhere)
# ---------------------------------------------------------------------------

from synapseml_tpu.core.params import Param, TypeConverters  # noqa: E402
from synapseml_tpu.core.pipeline import (PipelineModel,  # noqa: E402
                                         Transformer)


class BodyToFeatures(Transformer):
    din = Param("din", "feature width", default=DIN,
                converter=TypeConverters.to_int)

    def _transform(self, df):
        d = self.get("din")

        def per_part(p):
            out = dict(p)
            feats = np.zeros((len(p["body"]), d), np.float32)
            for i, body in enumerate(p["body"]):
                if isinstance(body, dict) and "features" in body:
                    feats[i] = np.asarray(body["features"], np.float32)
            out["features"] = feats
            return out

        return df.map_partitions(per_part)


class PredToReply(Transformer):
    def _transform(self, df):
        def per_part(p):
            out = dict(p)
            out["reply"] = np.asarray(
                [{"pred": int(p["pred"][i]),
                  "probs": [round(float(x), 6) for x in p["probs"][i]]}
                 for i in range(len(p["pred"]))], dtype=object)
            return out

        return df.map_partitions(per_part)


def build_pipeline(seed=0):
    from synapseml_tpu.onnx import ONNXModel
    from synapseml_tpu.onnx import proto as P
    from synapseml_tpu.onnx.proto import (AttributeProto, GraphProto,
                                          ModelProto, NodeProto,
                                          ValueInfoProto, numpy_to_tensor)

    rs = np.random.default_rng(seed)

    def node(op, inputs, outputs, **attrs):
        return NodeProto(input=list(inputs), output=list(outputs),
                         op_type=op,
                         attribute=[AttributeProto.make(k, v)
                                    for k, v in attrs.items()])

    nodes, inits = [], []
    prev, prev_w = "x", DIN
    for layer in range(DEPTH):
        w = rs.normal(size=(prev_w, WIDTH)).astype(np.float32) * 0.2
        b = rs.normal(size=(WIDTH,)).astype(np.float32) * 0.1
        inits += [numpy_to_tensor(w, f"W{layer}"),
                  numpy_to_tensor(b, f"b{layer}")]
        nodes += [node("Gemm", [prev, f"W{layer}", f"b{layer}"],
                       [f"h{layer}_pre"]),
                  node("Relu", [f"h{layer}_pre"], [f"h{layer}"])]
        prev, prev_w = f"h{layer}", WIDTH
    w = rs.normal(size=(prev_w, DOUT)).astype(np.float32) * 0.2
    b = rs.normal(size=(DOUT,)).astype(np.float32) * 0.1
    inits += [numpy_to_tensor(w, "Wout"), numpy_to_tensor(b, "bout")]
    nodes += [node("Gemm", [prev, "Wout", "bout"], ["logits"]),
              node("Softmax", ["logits"], ["probs"], axis=-1)]
    g = GraphProto(
        name="deep_mlp", node=nodes, initializer=inits,
        input=[ValueInfoProto(name="x", elem_type=P.FLOAT,
                              dims=["N", DIN])],
        output=[ValueInfoProto(name="probs", elem_type=P.FLOAT,
                               dims=["N", DOUT])],
    )
    onnx = ONNXModel(ModelProto(graph=g).encode(),
                     feed_dict={"x": "features"},
                     fetch_dict={"probs": "probs"},
                     argmax_dict={"probs": "pred"},
                     mini_batch_size=BUCKETS[-1])
    return PipelineModel(stages=[BodyToFeatures(din=DIN), onnx,
                                 PredToReply()])


def sample_rows(n=4, seed=7):
    rs = np.random.default_rng(seed)
    return [{"features": [round(float(x), 6) for x in
                          rs.normal(size=DIN)]} for _ in range(n)]


# ---------------------------------------------------------------------------
# grandchild drivers (fresh processes, cold caches)
# ---------------------------------------------------------------------------

def publish_driver(store: str) -> None:
    from synapseml_tpu.registry import ModelRegistry

    t0 = time.perf_counter()
    ModelRegistry(store).publish(
        "coldstart", build_pipeline(), version="v1",
        aot={"rows": sample_rows(), "buckets": BUCKETS})
    print(json.dumps({"publish_s": round(time.perf_counter() - t0, 2)}))


def arm_driver(store: str, use_aot: bool) -> None:
    import urllib.request

    from synapseml_tpu.core import batching as cb
    from synapseml_tpu.core.pipeline import Transformer
    from synapseml_tpu.io.serving import serve_pipeline

    class Placeholder(Transformer):
        def _transform(self, df):
            def pp(p):
                out = dict(p)
                out["reply"] = np.asarray([{}] * len(p["id"]),
                                          dtype=object)
                return out

            return df.map_partitions(pp)

    srv = serve_pipeline(Placeholder(), batch_interval_ms=5, version="v0",
                         max_batch_rows=BUCKETS[-1])

    def post(path, payload, timeout=600):
        req = urllib.request.Request(
            srv.address + path, data=json.dumps(payload).encode(),
            method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    cache = cb.get_compiled_cache()
    misses0 = cache.miss_count("onnx_model")
    t0 = time.perf_counter()
    reply = post("/admin/load", {"registry": store, "model": "coldstart",
                                 "ref": "v1", "aot": use_aot})
    swap_wall_ms = (time.perf_counter() - t0) * 1e3
    # first post-swap request over HTTP (a rung both arms warmed)
    t0 = time.perf_counter()
    post("/", sample_rows(1, seed=77)[0])
    http_first_ms = (time.perf_counter() - t0) * 1e3
    # first rung-128 batch through the exact serve-loop preparation — the
    # drained burst a fleet cutover sees; the JIT arm's capped warmup
    # never compiled this rung
    from synapseml_tpu.io.serving import run_warmup

    loaded = srv.pipeline_holder.pipeline
    bodies = sample_rows(FIRST_BATCH, seed=1234)
    loop_cfg = {"parse_json": True, "input_col": "body"}
    t0 = time.perf_counter()
    run_warmup(loaded, bodies, [FIRST_BATCH], loop_cfg)
    first_batch_ms = (time.perf_counter() - t0) * 1e3
    # warm reference for the same batch (steady-state floor, min of 3)
    warm_ms = min(
        _timed(lambda: run_warmup(loaded, bodies, [FIRST_BATCH], loop_cfg))
        for _ in range(3))
    # deterministic probe replies for the byte-identity gate
    probes = [post("/", b) for b in sample_rows(8, seed=42)]
    print(json.dumps({
        "arm": "aot" if use_aot else "jit",
        "swap_wall_ms": round(swap_wall_ms, 2),
        "load_ms": reply["load_ms"],
        "warmup": reply["warmup"],
        "http_first_request_ms": round(http_first_ms, 2),
        "first_128_batch_ms": round(first_batch_ms, 2),
        "warm_128_batch_ms": round(warm_ms, 2),
        "traced_after_swap": cache.miss_count("onnx_model") - misses0,
        "probes": probes,
    }))
    srv.stop()


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return (time.perf_counter() - t0) * 1e3


def _grandchild(args: list, timeout_s: float) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench_dir = str(Path(__file__).parent)
    repo = str(Path(__file__).parent.parent)
    code = ("import sys; sys.path.insert(0, %r); sys.path.insert(0, %r); "
            "import deploy_coldstart as dc; dc.%s" %
            (bench_dir, repo, args[0]))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True,
                          timeout=timeout_s, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"grandchild {args[0]} failed:\n"
                           f"{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(jax, platform, n_chips):
    directory = tempfile.mkdtemp(prefix="synapseml_coldstart_")
    store = os.path.join(directory, "store")
    try:
        pub = _grandchild([f"publish_driver({store!r})"], 420)
        arms = {}
        for use_aot in (True, False):
            out = _grandchild(
                [f"arm_driver({store!r}, {use_aot})"], 420)
            arms[out["arm"]] = out
        aot, jit = arms["aot"], arms["jit"]
        identical = (json.dumps(aot["probes"], sort_keys=True)
                     == json.dumps(jit["probes"], sort_keys=True))
        ratio_first = (round(aot["first_128_batch_ms"]
                             / jit["first_128_batch_ms"], 3)
                       if jit["first_128_batch_ms"] else None)
        ratio_swap = (round(aot["load_ms"] / jit["load_ms"], 3)
                      if jit["load_ms"] else None)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    return {
        "metric": "deploy cold-start first rung-128 batch after hot swap, "
                  "AOT vs JIT warmup"
                  + ("" if platform == "tpu" else " (CPU A/B)"),
        "value": aot["first_128_batch_ms"], "unit": "ms",
        "lower_is_better": True,
        # the subprocess arms force CPU so publish/load fingerprints match
        "platform": "cpu",
        "publish_s": pub["publish_s"],
        "ladder": BUCKETS, "first_batch_rows": FIRST_BATCH,
        "aot": aot, "jit": jit,
        "first_batch_aot_vs_jit": ratio_first,
        "swap_wall_aot_vs_jit": ratio_swap,
        "aot_zero_traces": aot["warmup"]["executables_traced"] == 0
        and aot["traced_after_swap"] == 0,
        "outputs_equal": identical,
    }


def main():
    from _common import init_jax

    jax, platform, n_chips = init_jax()
    print(json.dumps(run(jax, platform, n_chips)))


if __name__ == "__main__":
    main()
