"""Share of the window that `Trainer.fit`'s dispatch loop waited on its chunk
queue: the sum of the program's own `train.chunk_wait` spans."""

from perfbench.lib import program_spans


def read(facts: dict):
    return program_spans.share(facts, program_spans.total_s("train.chunk_wait"))
