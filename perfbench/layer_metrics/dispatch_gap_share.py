"""The host's own account of what the device idles for: over the window's
dispatch cycles, the time from the end of `train.fetch` n (the device has
finished dispatch n) to the end of `train.dispatch` n+1 (the next program is
enqueued), over the window."""

from perfbench.lib import program_spans


def _gaps_s(children: dict):
    dispatches = children.get("train.dispatch", [])
    fetches = children.get("train.fetch", [])
    if len(fetches) != len(dispatches):
        return None
    return sum(program_spans.end_ns(nxt) - program_spans.end_ns(fetch)
               for fetch, nxt in zip(fetches, dispatches[1:])) / 1e9


def read(facts: dict):
    return program_spans.share(facts, _gaps_s)
