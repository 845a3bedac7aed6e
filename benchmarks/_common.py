"""Shared setup for benchmark scripts."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))


def init_jax():
    """Import jax on the platform it finds (``JAX_PLATFORMS`` is jax's own
    variable), with the persistent compile cache on.
    Returns (jax module, platform string, device count)."""
    import jax

    from synapseml_tpu.core.platform import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    return jax, devs[0].platform, len(devs)


from synapseml_tpu.core.pipeline import Transformer as _Transformer


class EchoT(_Transformer):
    """Picklable trivial Transformer for serving benchmarks (module-level so
    worker processes can unpickle it by reference)."""

    def _transform(self, df):
        import numpy as np

        def per_part(p):
            out = dict(p)
            out["reply"] = np.asarray([{"ok": True} for _ in p["body"]],
                                      dtype=object)
            return out

        return df.map_partitions(per_part)


class GBDTScorerT(_Transformer):
    """Picklable MODEL-BACKED serving payload: a fitted GBDT classifier
    scores each request's ``features`` list — the non-trivial pipeline the
    latency claims should be judged against (a real tree-ensemble forward
    per request, not an echo)."""

    def __init__(self, model, **kw):
        super().__init__(**kw)
        self._model = model

    def _transform(self, df):
        import numpy as np

        from synapseml_tpu.core import DataFrame

        def per_part(p):
            feats = np.asarray([np.asarray(b["features"], np.float32)
                                for b in p["body"]])
            scored = self._model.transform(
                DataFrame.from_dict({"features": feats}))
            preds = scored.collect_column("prediction")
            out = dict(p)
            out["reply"] = np.asarray([{"prediction": float(v)}
                                       for v in preds], dtype=object)
            return out

        return df.map_partitions(per_part)


def train_tiny_gbdt(seed: int = 0):
    """A quickly-fitted GBDT classification model for serving benches."""
    import numpy as np

    from synapseml_tpu.core import DataFrame
    from synapseml_tpu.gbdt import LightGBMClassifier

    rs = np.random.default_rng(seed)
    X = rs.normal(size=(400, 8)).astype(np.float32)
    y = (X @ rs.normal(size=8) > 0).astype(np.int32)
    df = DataFrame.from_dict({"features": X, "label": y})
    return LightGBMClassifier(num_iterations=20, num_leaves=15,
                              max_bin=63).fit(df)
