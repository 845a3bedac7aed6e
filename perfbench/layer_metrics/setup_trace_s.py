"""Seconds of Python tracing and lowering to MLIR during set-up, from jax's
monitoring events; the persistent cache saves none of it."""


def read(facts: dict):
    return facts["setup_split"]["trace_s"] + facts["setup_split"]["lower_s"]
