"""A reader of the profiler's `.xplane.pb` (tsl/profiler/protobuf/xplane.proto)
on the protobuf wire format, with no dependency. `jax.profiler.ProfileData`
reads events but not the event metadata's stats, and `hlo_category`, which
tells a matrix product from other fusions, is kept there.

Fields read (field numbers of xplane.proto):
  XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5;
  XLine.name=2 .timestamp_ns=3 .events=4; XEvent.metadata_id=1 .offset_ps=2
  .duration_ps=3; XEventMetadata.id=1 .name=2 .stats=5; XStatMetadata.id=1
  .name=2; XStat.metadata_id=1 .str_value=5 .ref_value=7; map entries key=1
  value=2.
"""

from __future__ import annotations


def _varint(buf, pos: int):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """Yield (field number, wire type, value) of one message; a
    length-delimited value is a memoryview."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf) -> dict:
    out = {}
    for number, _, value in _fields(buf):
        if number == 1:
            out["id"] = value
        elif number == 5:
            out["str"] = _text(value)
        elif number == 7:
            out["ref"] = value
    return out


def _event_metadata(buf) -> dict:
    out = {"name": "", "stats": []}
    for number, _, value in _fields(buf):
        if number == 1:
            out["id"] = value
        elif number == 2:
            out["name"] = _text(value)
        elif number == 5:
            out["stats"].append(_stat(value))
    return out


def _map_entry(buf):
    key = value = None
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _line(buf) -> dict:
    out = {"name": "", "timestamp_ns": 0, "events": []}
    for number, _, value in _fields(buf):
        if number == 2:
            out["name"] = _text(value)
        elif number == 3:
            out["timestamp_ns"] = value
        elif number == 4:
            meta = offset = duration = 0
            for n, _, v in _fields(value):
                if n == 1:
                    meta = v
                elif n == 2:
                    offset = v
                elif n == 3:
                    duration = v
            out["events"].append((meta, offset, duration))
    return out


def read(path: str, want_plane=lambda name: True) -> list:
    """[{'name': plane, 'lines': [{'name': line, 'events':
    [(name, start_ns, end_ns, hlo_category)]}]}] of the planes wanted."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for number, _, value in _fields(buf):
        if number != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for n, _, v in _fields(value):
            if n == 2:
                name = _text(v)
        if not want_plane(name):
            continue
        for n, _, v in _fields(value):
            if n == 3:
                lines.append(_line(v))
            elif n == 4:
                key, meta = _map_entry(v)
                event_meta[key] = _event_metadata(meta)
            elif n == 5:
                key, meta = _map_entry(v)
                for m, _, mv in _fields(meta):
                    if m == 2:
                        stat_names[key] = _text(mv)
        category_of = {}
        for key, meta in event_meta.items():
            category = ""
            for stat in meta["stats"]:
                if stat_names.get(stat.get("id")) == "hlo_category":
                    category = stat.get("str") or stat_names.get(stat.get("ref"), "")
            category_of[key] = category
        out_lines = []
        for line in lines:
            base = line["timestamp_ns"] * 1000
            out_lines.append({"name": line["name"], "events": [
                (event_meta.get(meta, {}).get("name", ""),
                 (base + offset) / 1000.0, (base + offset + duration) / 1000.0,
                 category_of.get(meta, ""))
                for meta, offset, duration in line["events"]]})
        planes.append({"name": name, "lines": out_lines})
    return planes
