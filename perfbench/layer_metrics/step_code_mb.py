"""Bytes of generated code of the scanned step the window runs, by XLA's own
count: the program's gauge `synapseml_train_program_bytes{program="scan",
kind="code"}`. What the step weighs in the compile cache and on the device."""

from perfbench.lib import compile_record


def read(facts: dict):
    code = compile_record.scan_bytes(facts, "code")
    return None if code is None else code / 1e6
