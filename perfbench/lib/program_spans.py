"""The fit loop's own account of a window: the spans and counters that
`synapseml_tpu/models/trainer.py` records inside `Trainer.fit`, as the
per-layer metrics `chunk_wait_share`, `chunk_build_share`, `place_share`,
`dispatch_gap_share` and `scan_step_compiles` read them.

`Trainer.fit` opens one `train.fit` root span a call and, under it, a span at
every boundary of its dispatch loop: `train.chunk_wait` (the loop waits for
the chunk producer), `train.place` (host to device), `train.dispatch` (the
jitted call, back at enqueue), `train.fetch` (the loop waits for the device)
and, from the producer's thread, `train.chunk_build`. A span has `start_ns`
(Unix-epoch nanoseconds) and `duration_ms`; a profiler trace counts its times
from the `profile_start_time` stat of its `Task Environment` plane, epoch
nanoseconds too, so `start_ns - profile_start_ns(path)` is a span's place in
that trace.

A program that records none of this (a parent commit) gives `None` everywhere,
and the result line leaves the metric out.
"""

from __future__ import annotations

ROOT = "train.fit"
SLACK = 0.02      # the root span against the harness's own clock on the window
COMPILES = 'synapseml_train_step_compiles_total{program="scan"}'


def finished_spans() -> list:
    """The process tracer's ring of finished spans, oldest first."""
    from synapseml_tpu.core import observability as obs

    return obs.get_tracer().finished_spans()


def end_ns(span) -> int:
    """In whole nanoseconds: a float holds an epoch time to 256 ns only."""
    return span.start_ns + int(span.duration_ms * 1e6)


def window(facts: dict):
    """{span name: [the span's of that name, by start]} of the children of the
    last `train.fit` root, with the root itself under `train.fit`. `None`
    unless that root is the measured window: its duration within 2% of
    `facts["window_s"]` and exactly `facts["dispatches"]` `train.dispatch`
    children. A ring that overflowed or a fit that is not the window's leaves
    a metric out, never wrong."""
    spans = finished_spans()
    roots = [s for s in spans if s.name == ROOT]
    if not roots:
        return None
    root = max(roots, key=lambda s: s.start_ns)
    if abs(root.duration_ms / 1e3 - facts["window_s"]) > SLACK * facts["window_s"]:
        return None
    children: dict = {ROOT: [root]}
    for s in spans:
        if s.parent_id == root.span_id and s.trace_id == root.trace_id:
            children.setdefault(s.name, []).append(s)
    for group in children.values():
        group.sort(key=lambda s: s.start_ns)
    if len(children.get("train.dispatch", ())) != facts["dispatches"]:
        return None
    return children


def share(facts: dict, seconds) -> float | None:
    """100 x `seconds(children)` over the window; `None` in a rehearsal (a CPU
    gives no times), without the window's spans, or where `seconds` finds
    nothing to add up."""
    if facts["peaks"] is None:
        return None
    children = window(facts)
    if children is None:
        return None
    total = seconds(children)
    return None if total is None else 100.0 * total / facts["window_s"]


def total_s(name: str):
    """Sum of the durations of the children called `name`."""
    return lambda children: sum(s.duration_ms for s in children.get(name, ())) / 1e3


def counter(facts: dict, series: str):
    """A series of the process's metrics registry; `None` in a rehearsal or
    where the program has no such series."""
    if facts["peaks"] is None:
        return None
    from synapseml_tpu.core import observability as obs

    return obs.get_registry().snapshot().get(series)


def profile_start_ns(path: str) -> int | None:
    """`profile_start_time` of an `.xplane.pb`: the epoch nanoseconds from
    which every line of the trace counts (XPlane.stats=6; XStat.uint64_value=3
    .int64_value=4)."""
    from . import xplane

    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for number, _, plane in xplane._fields(buf):
        if number != 1:
            continue
        name, stats, stat_names = "", [], {}
        for n, _, v in xplane._fields(plane):
            if n == 2:
                name = xplane._text(v)
            elif n == 6:
                stats.append(v)
            elif n == 5:
                key, meta = xplane._map_entry(v)
                for m, _, mv in xplane._fields(meta):
                    if m == 2:
                        stat_names[key] = xplane._text(mv)
        if name != "Task Environment":
            continue
        for stat in stats:
            fields = {n: v for n, _, v in xplane._fields(stat)}
            if stat_names.get(fields.get(1)) == "profile_start_time":
                return int(fields.get(3, fields.get(4)))
    return None
