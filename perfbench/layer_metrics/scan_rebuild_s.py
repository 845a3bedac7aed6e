"""Seconds jax spent building the scanned step again for a signature after its
first (`train.compile` spans of `scan` with `signature` >= 2): what a job pays
because a fresh state's type is not the step's own output's (PERF.md, section
7). 0.0 where the step has one signature."""

from perfbench.lib import compile_record


def read(facts: dict):
    return compile_record.build_s(facts, from_signature=2)
