"""Launches and device time of one kernel, from a profiler trace: the events
of the first device plane's `XLA Ops` line whose `hlo_category` is a custom
call and whose name path (`tf_op` stat of the event's metadata,
`lib/scope_times.py`) holds `scope` and one of `marks` (a Pallas kernel's path
ends in `pallas_call`; the pads and reshapes around it are other categories).
Also the device time under `scope` by `hlo_category`, which says what else ran
there.
"""

from __future__ import annotations

from . import xplane
from .trace_reduce import CONTAINER_OPS, DEVICE_PLANE_PREFIX, OPS_LINE


def read(path: str, scope: str, marks: tuple) -> dict | None:
    """{"launches", "seconds", "by_category": {category: seconds under scope}},
    whole trace; None without a device plane."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for number, _, plane in xplane._fields(buf):
        if number != 1:
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for n, _, v in xplane._fields(plane):
            if n == 2:
                name = xplane._text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                key, meta = xplane._map_entry(v)
                metas[key] = xplane._event_metadata(meta)
            elif n == 5:
                key, meta = xplane._map_entry(v)
                for m, _, mv in xplane._fields(meta):
                    if m == 2:
                        stat_names[key] = xplane._text(mv)
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        kind = {}       # metadata id -> (category, is the kernel) of ops under the scope
        for key, meta in metas.items():
            stats = {stat_names.get(s.get("id")): s.get("str") or stat_names.get(s.get("ref"), "")
                     for s in meta["stats"]}
            category, op = stats.get("hlo_category", ""), stats.get("tf_op") or ""
            if category in CONTAINER_OPS or scope not in op:
                continue
            kind[key] = (category or "?", "custom" in category and any(m in op for m in marks))
        launches, seconds, by_category = 0, 0.0, {}
        for line in lines:
            parsed = xplane._line(line)
            if parsed["name"] != OPS_LINE:
                continue
            for meta, _, duration_ps in parsed["events"]:
                if meta not in kind:
                    continue
                category, is_kernel = kind[meta]
                by_category[category] = by_category.get(category, 0.0) + duration_ps / 1e12
                if is_kernel:
                    launches += 1
                    seconds += duration_ps / 1e12
        return {"launches": launches, "seconds": seconds, "by_category": by_category}
    return None
