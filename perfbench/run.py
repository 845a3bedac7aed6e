"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the machine it is started on and prints,
as the last line of standard output, one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` `breakdown`, and last `checks`
(each number compared beside its limit; also the last lines of standard error).

It refuses to measure without a TPU: exit code 2 and no result line. The one
exception is a rehearsal of the control flow, `--rehearse-cpu`, which needs
`JAX_PLATFORMS=cpu`, runs the sizes of `perfbench/rehearsal/overrides.json`,
and says `cpu` in `device`.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a driver gets from the harness: the process clock, the compile
    meter and, in a traced run, the tracer."""

    def __init__(self, args, meter):
        self.args = args
        self.meter = meter
        self.t_process = T_PROCESS
        self.setup_s = None

    def start_tracer(self):
        if not self.args.trace:
            return None
        from perfbench.lib.tracer import WindowTracer

        out = os.path.join(ROOT, ".perfbench_out", "trace")
        return WindowTracer(out, float(self.args.seconds)).start()


def _device_facts(chips: int, rehearse: bool) -> dict:
    """The devices as JAX reports them; exits 2 where they cannot run the cell."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu" or os.environ.get("JAX_PLATFORMS", "") != "cpu":
            sys.exit("perfbench: --rehearse-cpu runs only under JAX_PLATFORMS=cpu")
    elif platform != "tpu":
        print(f"perfbench: JAX found platform {platform!r}, not a TPU: a cell is "
              "measured on the chip or not at all", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"perfbench: the cell asks for {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="control-flow rehearsal at tiny sizes; never a device run")
    args = ap.parse_args(argv)

    from perfbench.lib.manifest import Cell, load_manifest

    manifest = load_manifest()
    cell = Cell(manifest, args.workload, rehearse=args.rehearse_cpu)
    if not os.path.isdir(os.path.join(ROOT, "synapseml_tpu")):
        print("perfbench: no system under test beside BENCHMARK.json "
              "(synapseml_tpu/ is missing)", file=sys.stderr)
        return 2

    import jax

    from synapseml_tpu.core.platform import enable_compile_cache

    enable_compile_cache()    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    # every program, however quick to compile, comes from the cache on a second run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = _device_facts(cell.chips, args.rehearse_cpu)

    from perfbench.lib.compile_meter import CompileMeter
    from perfbench.lib.peaks import peaks_for

    peaks = None if args.rehearse_cpu else peaks_for(device["kind"])
    ctx = Context(args, CompileMeter())
    driver = cell.module("drivers", cell.traffic["driver"])
    facts = driver.run(cell, args, ctx)
    facts.update(cell=cell, peaks=peaks, setup_s=ctx.setup_s, chips=cell.chips)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(group):
        reader = cell.module("layer_metrics" if args.trace else "end_metrics",
                             m["name"])
        value = reader.read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device["memory_peak_bytes"] = facts["memory_peak_bytes"]
    result = {"correct": bool(facts["verdict"]["correct"]) and facts["failed"] == 0,
              "attempted": facts["attempted"], "failed": facts["failed"],
              "metrics": metrics, "device": device}
    if args.trace and facts["trace"] is None and not args.rehearse_cpu:
        print("perfbench: the trace holds no device plane", file=sys.stderr)
        return 1
    if args.trace and facts["trace"] is not None:
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        result["breakdown"] = facts["trace"]["breakdown"]
    result["notes"] = facts["notes"]
    result["setup"] = facts["setup_split"]     # jax's own compile accounting
    result["reference_s"] = facts["reference_s"]
    result["checks"] = facts["verdict"]["checks"]

    for note in facts["notes"]:
        print(f"perfbench: {note}", file=sys.stderr)
    for name, c in facts["verdict"]["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}"
              + (f" at {c['leaf']}" if c.get("leaf") else ""), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
