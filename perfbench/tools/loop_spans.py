"""What the fit loop's own spans say of a cell, beyond the five per-layer
metrics: made the readings of PERF.md section 6 (PR 27). Run on the chip:

    chiprun -- python3 perfbench/tools/loop_spans.py report --workload <cell> \
        --seed <n> --seconds 20 --trace 1
    chiprun -- python3 perfbench/tools/loop_spans.py clock --workload <cell> --seed <n>

`report` is `perfbench/run.py` with the same arguments (its result line comes
first), then one JSON line `{"loop_spans": ...}`: of the window's `train.fit`
root, each child span's count, mean and largest milliseconds, the spans a
dispatch, the dispatches that compiled, and the cost of one empty span on this
host (a loop of 10,000).

`clock` runs a few dispatches of the cell's own trainer and loader with the
profiler's host tracer ON (level 1; only where a dispatch places little, as
`bert_base.finetune` does: PERF.md section 6 says what it does to ViT) and sets
side by side, for each `train.dispatch`, the `TraceAnnotation`'s times in the
trace and the span's `start_ns - profile_start_time`, and the interval from the
span's end to the start of the program's run on the device (`XLA Modules`).
"""

from __future__ import annotations

import argparse
import glob
import itertools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.lib import program_spans  # noqa: E402


def span_cost_us(n: int = 10_000) -> float:
    """Microseconds one empty loop span costs on this host: the span, the
    annotation and the histogram observation. Fills the process tracer's ring,
    so it comes after the reading."""
    from synapseml_tpu.models import trainer

    t0 = time.perf_counter()
    for _ in range(n):
        with trainer._LoopSpan("train.fetch"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def last_fit(spans: list) -> dict:
    """Children of the last `train.fit` root by name, whatever its length."""
    root = [s for s in spans if s.name == program_spans.ROOT][-1]
    out: dict = {program_spans.ROOT: [root]}
    for s in sorted(spans, key=lambda s: s.start_ns):
        if s.parent_id == root.span_id:
            out.setdefault(s.name, []).append(s)
    return out


def summary(children: dict) -> dict:
    root = children[program_spans.ROOT][0]
    dispatches = children.get("train.dispatch", [])
    table = {}
    for name, group in children.items():
        ms = [s.duration_ms for s in group]
        table[name] = {"count": len(ms), "mean_ms": sum(ms) / len(ms), "max_ms": max(ms),
                       "sum_ms": sum(ms)}
    builds = children.get("train.chunk_build", [])
    for key in ("next_ms", "stack_ms", "put_wait_ms"):
        values = [s.attributes[key] for s in builds]
        if values:
            table["train.chunk_build"][key] = {
                "mean": sum(values) / len(values), "max": max(values)}
    places = children.get("train.place", [])
    n_spans = sum(len(g) for name, g in children.items() if name != program_spans.ROOT)
    return {"root_ms": root.duration_ms, "root_attributes": root.attributes,
            "spans": table,
            "spans_a_dispatch": n_spans / max(len(dispatches), 1),
            "place_bytes": places[0].attributes["bytes"] if places else None,
            "compiled_dispatches": [s.attributes["first_step"] for s in dispatches
                                    if s.attributes["compiled"]]}


def report(argv: list) -> int:
    from perfbench import run

    rc = run.main(argv)
    if rc:
        return rc
    out = summary(last_fit(program_spans.finished_spans()))
    out["empty_span_us"] = span_cost_us()
    print(json.dumps({"loop_spans": out}))
    return 0


def clock(argv: list) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dispatches", type=int, default=6)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU: the control flow only")
    args = ap.parse_args(argv)

    import jax

    from perfbench.drivers import train_window as tw
    from perfbench.lib import datagen, xplane
    from perfbench.lib.manifest import Cell, load_manifest
    from synapseml_tpu.core import observability as obs
    from synapseml_tpu.core.instrumentation import profile_trace
    from synapseml_tpu.core.platform import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = Cell(load_manifest(), args.workload, rehearse=args.rehearse_cpu)
    chunk = int(cell.traffic["scan_chunk"])
    adapter = cell.module("programs", cell.config["program"])
    data = datagen.make_rows(cell.config, cell.traffic, args.seed)
    trainer = tw.build_trainer(cell, adapter)
    state = trainer.resume_state(tw._device_weights(cell, adapter, trainer, args.seed))
    loader = tw.make_loader(trainer, data, cell.traffic, args.seed)
    out_dir = os.path.join(ROOT, ".perfbench_out", "clock")
    shutil.rmtree(out_dir, ignore_errors=True)
    try:
        it = iter(loader)
        state = trainer.fit(state, itertools.islice(it, 2 * chunk),
                            max_steps=2 * chunk, scan_chunk=chunk)   # both compiles
        jax.block_until_ready(state.params)
        obs.reset_tracer()
        n = args.dispatches * chunk
        with profile_trace(out_dir, host_tracer_level=1):
            state = trainer.fit(state, itertools.islice(it, n), max_steps=n,
                                scan_chunk=chunk)
            jax.block_until_ready(state.params)
    finally:
        loader.close()
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    origin = program_spans.profile_start_ns(path)
    planes = xplane.read(path, lambda name: name.startswith(("/host:", "/device:TPU:0")))
    annotated = sorted((a, b) for p in planes if p["name"].startswith("/host:")
                       for line in p["lines"] for name, a, b, _ in line["events"]
                       if name == "train.dispatch")
    modules = sorted((a, b, name) for p in planes if p["name"].startswith("/device:")
                     for line in p["lines"] if line["name"] == "XLA Modules"
                     for name, a, b, _ in line["events"])
    run_s = {}
    for a, b, name in modules:
        run_s[name] = run_s.get(name, 0.0) + b - a
    main_program = max(run_s, key=run_s.get) if run_s else None    # the scanned step
    starts = [a for a, _, name in modules if name == main_program]
    spans = [s for s in obs.get_tracer().finished_spans() if s.name == "train.dispatch"]
    spans.sort(key=lambda s: s.start_ns)
    rows = []
    for i, s in enumerate(spans):
        s0, s1 = s.start_ns - origin, program_spans.end_ns(s) - origin
        row = {"span_start_ns": s0, "span_end_ns": s1}
        if i < len(annotated):
            row.update(annotation_start_ns=annotated[i][0], annotation_end_ns=annotated[i][1],
                       start_diff_us=(s0 - annotated[i][0]) / 1e3,
                       end_diff_us=(s1 - annotated[i][1]) / 1e3)
        nxt = [a for a in starts if a >= s0]
        if nxt:
            row["end_to_module_start_ms"] = (nxt[0] - s1) / 1e6
        rows.append(row)
    shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({"clock": {
        "device": jax.devices()[0].device_kind, "profile_start_time": origin,
        "main_program": main_program, "module_runs": len(starts),
        "annotations": len(annotated), "dispatch_spans": rows}}))
    return 0


if __name__ == "__main__":
    command, rest = sys.argv[1], sys.argv[2:]
    sys.exit({"report": report, "clock": clock}[command](rest))
