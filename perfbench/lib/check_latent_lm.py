"""The comparison that decides `correct` for a latent-attention MoE decoder's
training cell: `lib/check.py`'s numbers (`gaps`) and its way of finding the
fed rows again (`reference_batches`), against the cell's reference (found by
the configuration's `program` name: `reference/latent_moe_lm.py`) following
the same first steps from the same seed; beside them
`constants_changed`, the count of the routers' selection-bias entries that
differ, bit for bit, from the seed's after the steps (exact, limit 0), as
`lib/check_hybrid_lm.py` has it. Only the numbers that the cell's limits file
names are compared. Limits in `perfbench/limits/<cell>.json`, the readings
they were set from in PERF.md."""

from __future__ import annotations

from .check import gaps, reference_batches


def compare_first_steps(cell, data: dict, kept: list, program: dict, seed: int) -> dict:
    ref = cell.module("reference", cell.config["program"])
    traffic = cell.traffic
    batches, bad_rows = reference_batches(cell.config, data, kept)
    want_steps = int(traffic["check_steps"])
    sizes = ref.sizes(cell.config)
    reference = ref.run_steps(
        sizes, traffic["optimizer"], seed, batches, precision="float32",
        rows_per_block=int(traffic["reference_rows_per_block"]))
    numbers = gaps(program, reference, ref.leaf_sizes(sizes))
    numbers["rows_unmatched"] = float(bad_rows)
    numbers["steps_missing"] = float(abs(want_steps - program["steps"])
                                     + abs(want_steps - len(kept)))
    numbers["constants_changed"] = float(program["constants_changed"])
    checks = {}
    for name, limit in cell.limits.items():
        checks[name] = {"value": numbers[name], "limit": limit}
        if name + "_leaf" in numbers:
            checks[name]["leaf"] = numbers[name + "_leaf"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks, "reference": reference}
