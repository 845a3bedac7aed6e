"""Record a small profiler trace of the program's scanned train step.

Made `perfbench/testdata/tiny_train.xplane.pb`, the recorded trace that the
trace reduction's test reads. Run on the chip:

    chiprun -- python3 perfbench/tools/record_trace.py chiprun_out/trace

It drives `Trainer.train_steps_scan` of a tiny BERT for a few dispatches. (The
recorded file also holds host spans, which the reduction no longer reads.)
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> None:
    import jax
    import numpy as np

    from synapseml_tpu.models.flax_nets.bert import BertClassifier, bert_tiny
    from synapseml_tpu.models.trainer import Trainer, TrainerConfig
    from synapseml_tpu.parallel.mesh import MeshConfig, create_mesh

    print("devices", jax.devices())
    cfg = bert_tiny(hidden=128, n_heads=2, mlp_dim=512, n_layers=2)
    trainer = Trainer(BertClassifier(cfg, num_classes=2),
                      create_mesh(MeshConfig()), TrainerConfig())
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 1024, (16, 64), dtype=np.int32),
             "attention_mask": np.ones((16, 64), np.int32),
             "labels": rng.integers(0, 2, (16,), dtype=np.int32)}
    state = trainer.init_state(batch)
    stacked = {k: np.stack([v] * 8) for k, v in batch.items()}
    state, m = trainer.train_steps_scan(state, stacked)
    jax.block_until_ready(m)

    def feeder():
        for _ in range(6):
            with jax.profiler.TraceAnnotation("next_batch"):
                time.sleep(0.002)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    t = threading.Thread(target=feeder)
    t.start()
    for _ in range(4):
        with jax.profiler.TraceAnnotation("dispatch"):
            state, m = trainer.train_steps_scan(state, stacked)
        with jax.profiler.TraceAnnotation("fetch"):
            np.asarray(m["loss"])
        time.sleep(0.003)
    t.join()
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    print("trace files", [(p, os.path.getsize(p)) for p in paths])
    shutil.copy(paths[0], os.path.join(out_dir, "tiny_train.xplane.pb"))
    shutil.rmtree(os.path.join(out_dir, "plugins"))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace")
