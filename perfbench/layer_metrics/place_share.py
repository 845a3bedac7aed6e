"""Share of the window that `Trainer.fit`'s dispatch loop spent placing chunks
on the device (`shard_stacked_batch`): the sum of the program's own
`train.place` spans."""

from perfbench.lib import program_spans


def read(facts: dict):
    return program_spans.share(facts, program_spans.total_s("train.place"))
