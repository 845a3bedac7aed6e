"""Readings that the limits of a latent-attention MoE cell are set from
(`tools/limits_hybrid_lm.py`'s twin for `drivers/latent_lm_train_window.py`;
the driver and the reference are found by the names in the cell's traffic and
configuration files, the planted faults are the reference's `FAULTS`).

    python3 perfbench/tools/limits_latent_lm.py --workload <cell> --seeds 8 \
        --control-seeds 3 --out chiprun_out/limits_<cell>.jsonl

In ONE process, at the cell's own sizes, through the driver's own functions:
the program's first `check_steps` (through the loader and `Trainer.fit`)
against the float32 reference on every seed; on the first `--control-seeds`
of them also the fp8 control and the planted faults (routing weights neither
normalised nor scaled; the shared expert left out; the rotation applied to
all query and key dims; the latent not normed; the second half of every
batch's rows left out), each in the program's place
on the same batches. `--kinds` names the ones to read (default: all);
`--seeds 0` reads them alone, on batches drawn from the cell's own loader
without the program. One JSON line a reading.
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
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_480_000)
    ap.add_argument("--kinds", default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    import jax

    from perfbench.drivers import train_window as tw
    from perfbench.lib import check, datagen_lm
    from perfbench.lib.manifest import Cell, load_manifest
    from synapseml_tpu.core.platform import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("limits: readings are taken on the chip", file=sys.stderr)
        return 2
    cell = Cell(load_manifest(), args.workload, rehearse=args.rehearse_cpu)
    driver = cell.module("drivers", cell.traffic["driver"])
    ref = cell.module("reference", cell.config["program"])
    others = {"control_fp8": {"precision": "fp8"},
              **{f"fault_{name}": {name: True} for name in ref.FAULTS}}
    kinds = list(others) if args.kinds is None else [k for k in args.kinds.split(",") if k]
    driver.merge_rehearsal(cell)
    traffic = cell.traffic
    chunk, steps = int(traffic["scan_chunk"]), int(traffic["check_steps"])
    adapter = cell.module("programs", cell.config["program"])
    sizes = ref.sizes(cell.config)
    block = int(traffic["reference_rows_per_block"])
    leaf_sizes = ref.leaf_sizes(sizes)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    with open(args.out, "a") as out:
        def emit(**row):
            row.update(workload=args.workload, device=jax.devices()[0].device_kind)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps({k: v for k, v in row.items()
                              if k not in ("reference", "program")}), flush=True)

        trainer = driver.build_trainer(cell, adapter)
        probe = tw.DispatchProbe(trainer)
        kept_by_seed = {}
        for n in range(max(args.seeds, args.control_seeds)):   # the program first: its
            seed = args.first_seed + 7919 * n   # state and the reference's do not fit side by side
            t0 = time.perf_counter()
            data = datagen_lm.make_rows(cell.config, traffic, seed)
            loader = tw.make_loader(trainer, data, traffic, seed)
            fed = tw.FedIterator(iter(loader), chunk, keep=steps)
            program = None
            try:
                if n < args.seeds:
                    probe.first_metrics.clear()
                    probe.keep_metrics = True
                    state = driver.start_state(cell, adapter, trainer, seed)
                    constants = driver.host_constants(state)
                    state = trainer.fit(state, fed.phase(batches=steps), max_steps=steps,
                                        scan_chunk=chunk)
                    program = driver.first_dispatch_numbers(cell, adapter, probe, state, seed,
                                                            constants)
                    del state
                else:
                    list(fed.phase(batches=steps))
            finally:
                loader.close()
            batches, bad = check.reference_batches(cell.config, data, fed.kept)
            kept_by_seed[seed] = (program, batches, bad, time.perf_counter() - t0)
        del trainer, probe
        for n, (seed, (program, batches, bad, seconds)) in enumerate(kept_by_seed.items()):
            t0 = time.perf_counter()
            run = lambda **kw: ref.run_steps(  # noqa: E731
                sizes, traffic["optimizer"], seed, batches, rows_per_block=block, **kw)
            reference = run()
            if program is not None:
                emit(kind="program", seed=seed, rows_unmatched=bad, program_s=seconds,
                     reference_s=time.perf_counter() - t0,
                     constants_changed=program["constants_changed"],
                     **check.gaps(program, reference, leaf_sizes),
                     reference=reference, program=program)
            if n < args.control_seeds:
                for kind in kinds:
                    t1 = time.perf_counter()
                    other = run(**others[kind])
                    emit(kind=kind, seed=seed, rows_unmatched=bad, program=other,
                         seconds=time.perf_counter() - t1,
                         **check.gaps(other, reference, leaf_sizes))
    return 0


if __name__ == "__main__":
    sys.exit(main())
