"""What `Trainer` records when one of its jitted steps gets an executable
(`synapseml_tpu/models/trainer.py`, `Trainer._built`), as the per-layer metrics
`scan_build_s`, `scan_rebuild_s`, `step_temp_gb`, `step_resident_gb` and
`step_code_mb` read it.

One `train.compile` span an executable, under the `train.dispatch` span under
which or right after which it was built: `program` (`scan` | `step`),
`signature` (1, 2, ... in the order the `Trainer` met them), `trace_ms`,
`lower_ms`, `backend_ms` (jax's own monitoring events while it was built, on
the building thread alone), `cache` (`hit` | `miss` | `off`) and, on the
executable the later dispatches run, XLA's memory analysis in bytes a device
(`arg_bytes`, `out_bytes`, `alias_bytes`, `temp_bytes`, `code_bytes`; `take_ms` is
the host time of reading them), which the gauge `synapseml_train_program_bytes{kind,program}` repeats.

A program that records none of this (a parent commit) gives `None`, and the
result line leaves the metric out.
"""

from __future__ import annotations

from perfbench.lib import program_spans

BYTES = 'synapseml_train_program_bytes{kind="%s",program="scan"}'


def build_s(facts: dict, from_signature: int = 1):
    """jax's seconds of tracing, lowering and compiling or loading the scanned
    step's executables from the `from_signature`-th on, over the whole run.
    `None` in a rehearsal (a CPU gives no times) or without the spans."""
    if facts["peaks"] is None:
        return None
    built = [s.attributes for s in program_spans.finished_spans()
             if s.name == "train.compile" and s.attributes.get("program") == "scan"]
    if not built:
        return None
    return sum(a["trace_ms"] + a["lower_ms"] + a["backend_ms"] for a in built
               if a["signature"] >= from_signature) / 1e3


def scan_bytes(facts: dict, kind: str):
    """Bytes a device of one kind (`args` | `outputs` | `aliased` | `temp` |
    `code`) of the scanned step's newest executable; `None` in a rehearsal or
    where the program has no such series."""
    return program_spans.counter(facts, BYTES % kind)
