"""Shared servable pipeline for the AOT deploy tests: JSON request bodies
-> features -> ONNX MLP (the CompiledCache-adopted stage whose executables
the registry AOT-compiles) -> reply dicts. Module-level classes so
publish/load round-trips by class reference across processes (subprocess
drivers add ``tests/`` to ``sys.path``)."""

import numpy as np

from synapseml_tpu.core.params import Param, TypeConverters
from synapseml_tpu.core.pipeline import PipelineModel, Transformer


class BodyToFeatures(Transformer):
    """Parsed request bodies (``{"features": [...]}``) -> a rectangular
    float32 ``features`` column."""

    din = Param("din", "feature width", default=4,
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
    """ONNX outputs -> one JSON-able reply dict per request row."""

    def _transform(self, df):
        def per_part(p):
            out = dict(p)
            preds = p["pred"]
            probs = p["probs"]
            out["reply"] = np.asarray(
                [{"pred": int(preds[i]),
                  "probs": [round(float(x), 6) for x in probs[i]]}
                 for i in range(len(preds))], dtype=object)
            return out

        return df.map_partitions(per_part)


class TunableAffine(Transformer):
    """Autotune-search target: two 'backends' computing the same affine
    shift, one artificially slow — the publish-time search must pin
    'fast' and /admin/load must re-apply the pin."""

    impl = Param("impl", "backend: fast | slow", default="slow",
                 validator=lambda v: v in ("fast", "slow"))
    _AUTOTUNE_PARAMS = {"impl": ("fast", "slow")}

    def _transform(self, df):
        if self.get("impl") == "slow":
            import time

            time.sleep(0.003)

        def per_part(p):
            out = dict(p)
            if "features" in p:
                out["features"] = np.asarray(p["features"],
                                             np.float32) + 0.0
            return out

        return df.map_partitions(per_part)


def make_mlp_onnx(din=4, dout=3, width=8, depth=2, seed=0,
                  mini_batch_size=64):
    """Hand-built ONNX MLP (no external onnx dependency — the repo's own
    proto codec); depth sets how much there is to compile."""
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
    prev, prev_w = "x", din
    for layer in range(depth):
        w = rs.normal(size=(prev_w, width)).astype(np.float32) * 0.3
        b = rs.normal(size=(width,)).astype(np.float32) * 0.1
        inits += [numpy_to_tensor(w, f"W{layer}"),
                  numpy_to_tensor(b, f"b{layer}")]
        nodes += [node("Gemm", [prev, f"W{layer}", f"b{layer}"],
                       [f"h{layer}_pre"]),
                  node("Relu", [f"h{layer}_pre"], [f"h{layer}"])]
        prev, prev_w = f"h{layer}", width
    w = rs.normal(size=(prev_w, dout)).astype(np.float32) * 0.3
    b = rs.normal(size=(dout,)).astype(np.float32) * 0.1
    inits += [numpy_to_tensor(w, "Wout"), numpy_to_tensor(b, "bout")]
    nodes += [node("Gemm", [prev, "Wout", "bout"], ["logits"]),
              node("Softmax", ["logits"], ["probs"], axis=-1)]
    g = GraphProto(
        name="mlp", node=nodes, initializer=inits,
        input=[ValueInfoProto(name="x", elem_type=P.FLOAT,
                              dims=["N", din])],
        output=[ValueInfoProto(name="probs", elem_type=P.FLOAT,
                               dims=["N", dout])],
    )
    return ONNXModel(ModelProto(graph=g).encode(),
                     feed_dict={"x": "features"},
                     fetch_dict={"probs": "probs"},
                     argmax_dict={"probs": "pred"},
                     mini_batch_size=mini_batch_size)


def build_pipeline(din=4, dout=3, width=8, depth=2, seed=0,
                   mini_batch_size=64):
    return PipelineModel(stages=[
        BodyToFeatures(din=din),
        make_mlp_onnx(din=din, dout=dout, width=width, depth=depth,
                      seed=seed, mini_batch_size=mini_batch_size),
        PredToReply(),
    ])


def sample_rows(n=4, din=4, seed=7):
    rs = np.random.default_rng(seed)
    return [{"features": [round(float(x), 6) for x in
                          rs.normal(size=din)]} for _ in range(n)]
