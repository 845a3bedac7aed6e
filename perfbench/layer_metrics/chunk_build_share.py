"""How busy the chunk producer's thread is: the time its `train.chunk_build`
spans spent inside `next()` on the loader and stacking the chunk (`next_ms` +
`stack_ms`; not `put_wait_ms`, its wait on a full queue), over the window. At
100% the loader sets the pace."""

from perfbench.lib import program_spans


def _busy_s(children: dict):
    return sum(s.attributes["next_ms"] + s.attributes["stack_ms"]
               for s in children.get("train.chunk_build", ())) / 1e3


def read(facts: dict):
    return program_spans.share(facts, _busy_s)
