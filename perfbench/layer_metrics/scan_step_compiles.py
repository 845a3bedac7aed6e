"""Times the scanned step's jitted callable met a new signature over the whole
run (traced, then compiled or loaded from the cache): the program's own
`synapseml_train_step_compiles_total{program="scan"}` at the end of the run."""

from perfbench.lib import program_spans


def read(facts: dict):
    value = program_spans.counter(facts, program_spans.COMPILES)
    return None if value is None else int(value)
