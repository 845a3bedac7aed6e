"""The reduction from a profiler trace (`.xplane.pb`) to numbers: the device's
busy union over whole dispatch cycles, the matmul events' time, the op table
and the idle gaps, each gap named by where it lies on the device's own
timeline: `between_dispatches` (no program was running: the device waited for
the host) or `inside_program`.

Read with `perfbench/lib/xplane.py`. What it keys on, as read by hand from a
v5e trace (PERF.md, section 3): device planes are named `/device:TPU:<n>`;
their line `XLA Ops` holds one event per executed HLO op, nested where an op
(`while`, `conditional`, `call`) holds others, and their line `XLA Modules`
one event per run of a compiled program; the `hlo_category` stat of an
op's event metadata names its kind, and matrix products are the categories
that contain `convolution` (XLA:TPU lowers `dot` to a convolution, alone or as
the root of a `convolution fusion`). The host's plane is not read: the tracer
records none (`perfbench/lib/tracer.py` says why).
"""

from __future__ import annotations

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINER_OPS = ("while", "conditional", "call")
MATMUL_MARK = "convolution"
GAP_BETWEEN, GAP_INSIDE = "between_dispatches", "inside_program"
MIN_GAP_NS = 10_000.0


def _union(intervals: list) -> list:
    """Merge [start, end) intervals; returns the disjoint sorted union."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals if b > lo and a < hi]


def _total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def _base_name(name: str) -> str:
    """`%fusion.123` -> `fusion.123`; HLO op names keep their numbers, so that
    the table tells one fusion from another."""
    return name.lstrip("%").split(" ")[0]


def read_planes(path: str) -> dict:
    """{'device': {plane: [(name, start_ns, end_ns, category)]} (HLO ops),
        'modules': {plane: [(name, start_ns, end_ns, '')]} (program runs)}."""
    from . import xplane

    device, modules = {}, {}
    for plane in xplane.read(path, lambda n: n.startswith(DEVICE_PLANE_PREFIX)):
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                device.setdefault(plane["name"], []).extend(line["events"])
            elif line["name"] == MODULES_LINE:
                modules.setdefault(plane["name"], []).extend(line["events"])
    return {"device": device, "modules": modules}


def _cycle_marks(modules: dict) -> list:
    """Start times of the runs of the program that took most of the device's
    time (the scanned step), on the first device plane that has any."""
    for events in modules.values():
        total = {}
        for name, a, b, _ in events:
            total[name] = total.get(name, 0.0) + (b - a)
        if total:
            main = max(total, key=total.get)
            return sorted(a for name, a, _, _ in events if name == main)
    return []


def reduce_planes(planes: dict, *, top: int = 10) -> dict:
    """Numbers of one traced window.

    The window holds whole dispatch cycles on the device's own clock: it runs
    from one start of the main program (`XLA Modules` line) to its last start
    in the trace, `cycles` runs later. The first run in the trace is left out
    where there are three or more: it may be cut by the trace's start, and the
    profiler's own start-up delays the dispatch after it. With fewer than two
    runs the window is the extent of the device's events and `cycles` is None.
    Times are averaged over the device planes.
    """
    device = planes["device"]
    if not device:
        return None
    marks = _cycle_marks(planes.get("modules", {}))
    if len(marks) >= 3:
        lo, hi, cycles = marks[1], marks[-1], len(marks) - 2
    elif len(marks) == 2:
        lo, hi, cycles = marks[0], marks[1], 1
    else:
        starts = [e[1] for evs in device.values() for e in evs]
        ends = [e[2] for evs in device.values() for e in evs]
        if not starts:
            return None
        lo, hi, cycles = min(starts), max(ends), None
    n = len(device)
    busy = matmul = 0.0
    ops, gaps = {}, []
    for events in device.values():
        merged = _clip(_union([[a, b] for _, a, b, _ in events]), lo, hi)
        busy += _total(merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
        for name, a, b, category in events:
            if b <= lo or a >= hi or category in CONTAINER_OPS:
                continue
            dur = min(b, hi) - max(a, lo)
            ops[_base_name(name)] = ops.get(_base_name(name), 0.0) + dur
            if MATMUL_MARK in category:
                matmul += dur
    running = _union([[a, b] for evs in planes.get("modules", {}).values()
                      for _, a, b, _ in evs])
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        if b - a < MIN_GAP_NS:
            break
        inside = _total(_clip(running, a, b)) > 0.5 * (b - a)
        named.append([GAP_INSIDE if inside else GAP_BETWEEN, (b - a) / 1e9])
    table = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "matmul_s": matmul / n / 1e9, "cycles": cycles,
            "breakdown": {"device_ops": [[k, v / n / 1e9] for k, v in table],
                          "idle_gaps": named}}


def reduce_file(path: str, **kw) -> dict:
    return reduce_planes(read_planes(path), **kw)
