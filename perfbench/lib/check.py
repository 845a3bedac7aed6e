"""The comparison that decides `correct` for a training cell.

What the timed path produced in its first dispatch (every step's loss and
gradient norm, the first moment and the parameters' change after it, on the
batches the loader really fed) against the plain float32 reference following
the same steps from the same seed. Each number has a limit of its own, kept in
`perfbench/limits/<cell>.json` with the readings it was set from in PERF.md.
"""

from __future__ import annotations

import statistics

import numpy as np

from . import datagen

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone (a key's bias under softmax): left out of the change
QUIET_LEAF = 1e-3
# a leaf of fewer numbers than this (the classifier's bias: 2 or 10) is a
# handful of batch means whose moving sum over the steps cancels at random, so
# its moment's norm swings tenfold from seed to seed (PERF.md, section 2): left
# out of the moment, kept in the change, which Adam's scaling makes steady
SMALL_LEAF = 64


def gaps(got: dict, ref: dict, leaf_sizes: dict) -> dict:
    """The numbers compared, each the gap between a norm (or loss) of `got`
    and the reference's, never the norm of a difference:
    `loss_gap`, `grad_norm_gap`: every step's gap relative to the reference,
    as the root of their mean square (the worst step alone swings from seed to
    seed by its nature: PERF.md, section 2);
    `moment_gap`, `change_gap`: the worst leaf's, relative to the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    out = {}
    for name, key in (("loss_gap", "loss"), ("grad_norm_gap", "grad_norm")):
        a, b = np.asarray(got[key], float), np.asarray(ref[key], float)
        if a.shape != b.shape:
            out[name] = float("inf")
            continue
        out[name] = float(np.sqrt(np.mean(np.square((a - b) / b))))
    moment = ref["moment_norm"]
    median_moment = statistics.median(moment.values())
    loud = [k for k, v in moment.items() if v >= QUIET_LEAF * median_moment]
    large = [k for k in moment if leaf_sizes[k] >= SMALL_LEAF]
    for name, key, leaves in (("moment_gap", "moment_norm", large),
                              ("change_gap", "change_norm", loud)):
        floor = statistics.median(ref[key][k] for k in leaves)
        worst, at = 0.0, None
        for k in leaves:
            gap = abs(got[key].get(k, 0.0) - ref[key][k]) / max(ref[key][k], floor)
            if not gap <= worst:       # NaN counts as worst
                worst, at = gap, k
        out[name] = float(worst)
        out[name + "_leaf"] = at
    # a NaN fails its limit like any number above it
    return {k: (float("inf") if isinstance(v, float) and np.isnan(v) else v)
            for k, v in out.items()}


def reference_batches(config: dict, data: dict, kept: list):
    """The reference's batches: the generated rows that the fed rows equal, in
    the loader's order. Returns them with the count of fed rows that are no
    generated row, are padding, or came twice."""
    index = datagen.RowIndex(data, "input_ids" if config["inputs"] == "text" else "x")
    seen, bad, batches = set(), 0, []
    for batch in kept:
        rows = []
        valid = np.asarray(batch.get("_valid", np.ones(len(batch["labels"]))))
        for r in range(len(batch["labels"])):
            i = index.find(batch, r)
            if i is None or i in seen or valid[r] != 1.0:
                bad += 1
                i = 0 if i is None else i
            seen.add(i)
            rows.append(i)
        rows = np.asarray(rows)
        batches.append({k: v[rows] for k, v in data.items()})
    return batches, bad


def compare_first_steps(cell, data: dict, kept: list, program: dict,
                        seed: int) -> dict:
    from perfbench.reference import encoder as ref

    traffic = cell.traffic
    batches, bad_rows = reference_batches(cell.config, data, kept)
    want_steps = int(traffic["check_steps"])
    reference = ref.run_steps(
        ref.sizes(cell.config), traffic["optimizer"], seed, batches,
        precision="float32", rows_per_block=int(traffic["reference_rows_per_block"]))
    numbers = gaps(program, reference, ref.leaf_sizes(ref.sizes(cell.config)))
    numbers["rows_unmatched"] = float(bad_rows)
    numbers["steps_missing"] = float(abs(want_steps - program["steps"])
                                     + abs(want_steps - len(kept)))
    checks = {}
    for name, limit in cell.limits.items():
        value = numbers[name]
        checks[name] = {"value": value, "limit": limit}
        if name + "_leaf" in numbers:
            checks[name]["leaf"] = numbers[name + "_leaf"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks, "reference": reference}
