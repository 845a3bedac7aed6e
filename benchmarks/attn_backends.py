"""einsum vs flash attention, BERT-base train step. Which backend is faster
on the chip is not measured (ROADMAP A3). Runs as a bench.py child (``run``)
or standalone (``main``)."""
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent))


def run(jax, platform, n_chips):
    from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_base, bert_tiny
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    on_tpu = platform == "tpu"
    # longest-T configs first: that is where the blockwise kernel can win
    shapes = ((2048, 2), (512, 8)) if on_tpu else ((32, 8),)
    results = {}
    for T, B in shapes:
        for impl in ("flash", "einsum"):
            base = bert_base() if on_tpu else bert_tiny()
            cfg = dataclasses.replace(base, attn_impl=impl)
            tr = Trainer(BertClassifier(cfg, num_classes=2),
                         create_mesh(MeshConfig(data=-1)),
                         TrainerConfig(learning_rate=5e-5, total_steps=1000))
            rng = np.random.default_rng(0)
            batch = {"input_ids": rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32),
                     "attention_mask": np.ones((B, T), np.int32),
                     "labels": rng.integers(0, 2, (B,)).astype(np.int32)}
            state = tr.init_state(batch)
            k = 16 if on_tpu else 4
            stacked = jax.tree.map(lambda x: np.broadcast_to(x, (k,) + x.shape).copy(), batch)
            st, m = tr.train_steps_scan(state, stacked)
            float(np.asarray(m["loss"])[-1])
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                st, m = tr.train_steps_scan(st, stacked)
                np.asarray(m["loss"])
                best = min(best, time.perf_counter() - t0)
            results[f"T{T}_{impl}_ms"] = round(best / k * 1e3, 2)
            print(f"# attn {impl} T={T}: {results[f'T{T}_{impl}_ms']} ms/step",
                  flush=True)
    t_long = shapes[0][0]
    result = {
        "metric": "attention backend BERT-base train step"
                  + ("" if on_tpu else " (CPU smoke)"),
        "value": results[f"T{t_long}_flash_ms"], "unit": "ms/step",
        "lower_is_better": True, "platform": platform,
        "longest_T": t_long,
        "flash_vs_einsum_longT": round(
            results[f"T{t_long}_einsum_ms"] / results[f"T{t_long}_flash_ms"], 3),
    }
    result.update(results)
    return result


def main():
    from _common import init_jax

    jax, platform, n_chips = init_jax()
    print(json.dumps(run(jax, platform, n_chips)))


if __name__ == "__main__":
    main()
