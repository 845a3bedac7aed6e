"""Higgs-1M-shaped GBDT training throughput on the TPU (BASELINE.md config:
LightGBM Higgs-1M, 100 iterations, binary)."""
import json, sys, time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent))

def run(jax, platform, n_chips):
    from synapseml_tpu.gbdt.booster import train_booster
    rng = np.random.default_rng(0)
    # full Higgs-1M shape on the chip; smoke scale elsewhere. AUC is computed
    # on a HELD-OUT tail (never passed to train_booster), not training rows.
    N, F = (1_000_000, 28) if platform == "tpu" else (50_000, 28)
    n_test = min(100_000, N // 5)
    X = rng.normal(size=(N + n_test, F)).astype(np.float32)
    w = rng.normal(size=F); w[F//2:] = 0
    logits = X @ w * 0.5 + rng.normal(size=N + n_test) * 0.5
    y = (logits > 0).astype(np.float32)
    n_iter = 100 if platform == "tpu" else 20
    t0 = time.perf_counter()
    booster = train_booster(X[:N], y[:N], objective="binary",
                            num_iterations=n_iter, learning_rate=0.1,
                            num_leaves=31, max_bin=255,
                            histogram_impl="segment")
    train_s = time.perf_counter() - t0
    n_pred = n_test
    t0 = time.perf_counter()
    p = booster.predict(X[-n_test:])  # the held-out tail
    pred_s = time.perf_counter() - t0
    auc_y, auc_p = y[-n_test:], np.asarray(p).ravel()
    from scipy.stats import rankdata
    ranks = rankdata(auc_p)  # average tied ranks (exact Mann-Whitney)
    n1 = auc_y.sum(); n0 = len(auc_y) - n1
    auc = (ranks[auc_y == 1].sum() - n1*(n1+1)/2) / (n1*n0)
    return {"metric": "LightGBM Higgs-1M train" if platform == "tpu"
            else "LightGBM 50k (CPU smoke)",
            "value": round(N * n_iter / train_s), "unit": "row-iters/sec",
            "platform": platform, "train_s": round(train_s, 2),
            "hist_impl": "segment",
            "pred_rows": n_pred, "pred_s": round(pred_s, 3),
            "auc": round(float(auc), 4)}


def main():
    from _common import init_jax

    jax, platform, n_chips = init_jax()
    print(json.dumps(run(jax, platform, n_chips)))


if __name__ == "__main__":
    main()
