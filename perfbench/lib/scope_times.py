"""Device time by `jax.named_scope`, from a profiler trace: the `tf_op` stat of
an `XLA Ops` event's metadata holds the op's name path (`jit(multi)/while/
body/.../attn.select/...`), so an op belongs to a scope whose name is in that
path. Reads the wire format with `lib/xplane.py`'s helpers (`xplane.read`
keeps `hlo_category` alone). A fused op is counted under its root's path.
Containers (`while`, `conditional`, `call`) span their bodies and are left out.
"""

from __future__ import annotations

import re

from . import xplane
from .trace_reduce import CONTAINER_OPS, DEVICE_PLANE_PREFIX, OPS_LINE


def _short(op: str) -> str:
    """A name path without what every op of the step shares, layers folded."""
    op = re.sub(r"layer_\d+", "layer_N", op.rstrip(":"))
    return "/".join(p for p in op.split("/") if p not in (
        "jit(multi)", "while", "body", "closed_call", "checkpoint",
        "rematted_computation"))[-160:]


def read(path: str, scopes: tuple, top: int = 12) -> dict | None:
    """{"total_s": device time of all ops, "scopes": {scope: seconds}, "ops":
    the `top` name paths by time [[path, seconds]]} over the first device
    plane's `XLA Ops` line, whole trace; None without one."""
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
        kind, path_of = {}, {}   # metadata id -> scope, "" (no scope) or None (container)
        for key, meta in metas.items():
            stats = {stat_names.get(s.get("id")): s.get("str") or stat_names.get(s.get("ref"), "")
                     for s in meta["stats"]}
            if stats.get("hlo_category", "") in CONTAINER_OPS:
                kind[key] = None
                continue
            op = stats.get("tf_op") or ""
            kind[key] = next((s for s in scopes if s in op), "")
            path_of[key] = _short(op) or stats.get("hlo_category", "") or "?"
        total, by_scope, by_path = 0.0, dict.fromkeys(scopes, 0.0), {}
        for line in lines:
            parsed = xplane._line(line)
            if parsed["name"] != OPS_LINE:
                continue
            for meta, _, duration_ps in parsed["events"]:
                scope = kind.get(meta, "")
                if scope is None:
                    continue
                total += duration_ps / 1e12
                by_path[path_of[meta]] = by_path.get(path_of[meta], 0.0) + duration_ps / 1e12
                if scope:
                    by_scope[scope] += duration_ps / 1e12
        if total:
            ops = sorted(by_path.items(), key=lambda kv: -kv[1])[:top]
            return {"total_s": total, "scopes": by_scope, "ops": [list(o) for o in ops]}
    return None
