"""Seconds jax spent tracing, lowering and compiling (or loading from the
cache) the scanned step, every signature of it, over the whole run: the
`trace_ms + lower_ms + backend_ms` of the program's own `train.compile` spans
of `scan`. The step's part of `setup_trace_s` + `setup_compile_s`."""

from perfbench.lib import compile_record


def read(facts: dict):
    return compile_record.build_s(facts)
