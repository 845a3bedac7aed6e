"""Bytes the scanned step's call holds across itself, a device: arguments plus
outputs less what the outputs alias (the donated state), from the program's own
gauge `synapseml_train_program_bytes{program="scan"}`: state, batch and
metrics."""

from perfbench.lib import compile_record


def read(facts: dict):
    args, outputs, aliased = (compile_record.scan_bytes(facts, kind)
                              for kind in ("args", "outputs", "aliased"))
    if None in (args, outputs, aliased):
        return None
    return (args + outputs - aliased) / 1e9
