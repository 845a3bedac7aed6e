"""Bytes of temporaries XLA gave the scanned step the window runs, a device:
the program's own gauge `synapseml_train_program_bytes{program="scan",
kind="temp"}`, from `memory_analysis()` of that executable. What XLA's
scheduler chose to hold at once, beside the state."""

from perfbench.lib import compile_record


def read(facts: dict):
    temp = compile_record.scan_bytes(facts, "temp")
    return None if temp is None else temp / 1e9
