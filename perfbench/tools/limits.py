"""Readings that the limits of `perfbench/limits/<cell>.json` are set from.

    python3 perfbench/tools/limits.py --workload <cell> --seeds 12 --control-seeds 3 \
        --out chiprun_out/limits_<cell>.jsonl

In ONE process, at the cell's own sizes, through the driver's own functions:
  * lower readings: for each seed, the program's first dispatch (through the
    loader and `Trainer.fit`) against the float32 reference;
  * upper readings: the control (the reference computed with fp8 matrix
    products) and the planted fault (half of every batch left out, the mean
    taken over the rest) against the float32 reference, on the same batches.
One JSON line a reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_480_000)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    import jax

    from perfbench.drivers import train_window as tw
    from perfbench.lib import check, datagen
    from perfbench.lib.manifest import Cell, load_manifest
    from perfbench.reference import encoder as ref
    from synapseml_tpu.core.platform import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("limits: readings are taken on the chip", file=sys.stderr)
        return 2
    cell = Cell(load_manifest(), args.workload, rehearse=args.rehearse_cpu)
    traffic = cell.traffic
    chunk, steps = int(traffic["scan_chunk"]), int(traffic["check_steps"])
    adapter = cell.module("programs", cell.config["program"])
    sizes = ref.sizes(cell.config)
    block = int(traffic["reference_rows_per_block"])
    leaf_sizes = ref.leaf_sizes(sizes)
    trainer = tw.build_trainer(cell, adapter)
    probe = tw.DispatchProbe(trainer)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    with open(args.out, "a") as out:
        def emit(**row):
            row.update(workload=args.workload, device=jax.devices()[0].device_kind)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps({k: v for k, v in row.items()
                              if k not in ("reference", "program")}))

        for n in range(args.seeds):
            seed = args.first_seed + 7919 * n
            t0 = time.perf_counter()
            data = datagen.make_rows(cell.config, traffic, seed)
            probe.first_metrics.clear()
            probe.keep_metrics = True
            state = trainer.resume_state(tw._device_weights(cell, adapter, trainer, seed))
            loader = tw.make_loader(trainer, data, traffic, seed)
            fed = tw.FedIterator(iter(loader), chunk, keep=steps)
            try:
                state = trainer.fit(state, fed.phase(batches=steps),
                                    max_steps=steps, scan_chunk=chunk)
            finally:
                loader.close()
            program = tw.first_dispatch_numbers(cell, adapter, probe, state, seed)
            del state
            batches, bad = check.reference_batches(cell.config, data, fed.kept)
            run = lambda **kw: ref.run_steps(  # noqa: E731
                sizes, traffic["optimizer"], seed, batches, rows_per_block=block, **kw)
            reference = run(precision="float32")
            emit(kind="program", seed=seed, rows_unmatched=bad,
                 seconds=time.perf_counter() - t0, **check.gaps(program, reference, leaf_sizes),
                 reference=reference,
                 program=program)
            if n < args.control_seeds:
                for kind, kw in (("control_fp8", {"precision": "fp8"}),
                                 ("fault_half_batch", {"precision": "float32",
                                                       "half_batch": True})):
                    other = run(**kw)
                    emit(kind=kind, seed=seed, program=other,
                         **check.gaps(other, reference, leaf_sizes))
    return 0


if __name__ == "__main__":
    sys.exit(main())
