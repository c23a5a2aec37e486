"""Read a jax.profiler ``.xplane.pb`` with nothing but the protobuf wire format.

``jax.profiler.ProfileData`` yields an event's own stats but not those of its
metadata, and on the TPU the ``hlo_category`` of an op lives in the metadata.
So this walks the XSpace message itself (tsl/profiler/protobuf/xplane.proto):

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
    XLine.name=2 .timestamp_ns=3 .events=4
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
    XEventMetadata.id=1 .name=2 .stats=5
    XStatMetadata.id=1 .name=2
    XStat.metadata_id=1 .double=2 .uint64=3 .int64=4 .str=5 .bytes=6 .ref=7

and yields the same ``Event`` tuples as ``chrome_trace.read_events``.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Tuple

from .trace import Event


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview slice, so skipping a sub-message costs nothing."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield num, wire, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    key, val = 0, None
    for num, wire, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif num in (3, 4):
            val = v
        elif num in (5, 6):
            val = _text(v)
        elif num == 7:
            val = stat_names.get(v, str(v))
    return stat_names.get(key, str(key)), val


def _map_entry(buf) -> Tuple[int, object]:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf, keep_host) -> Iterator[Event]:
    name, lines, event_meta, stat_meta = "", [], [], []
    for num, _, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            event_meta.append(v)
        elif num == 5:
            stat_meta.append(v)
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        key, val = _map_entry(entry)
        for num, _, v in _fields(val):
            if num == 2:
                stat_names[key] = _text(v)
    is_device = name.startswith("/device:")
    names: Dict[int, str] = {}
    cats: Dict[int, str] = {}
    for entry in event_meta:
        key, val = _map_entry(entry)
        for num, _, v in _fields(val):
            if num == 2:
                names[key] = _text(v)
            elif num == 5 and is_device:
                k, s = _stat(v, stat_names)
                if k == "hlo_category":
                    cats[key] = str(s)
    # the python tracer fills the host plane with "$file:line fn" events;
    # they are no layer's span and are dropped by metadata id, unparsed
    wanted = {k for k, n in names.items()
              if is_device or (keep_host and not n.startswith("$"))}
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for num, _, v in _fields(line):
            if num == 2:
                line_name = _text(v)
            elif num == 3:
                t0_ns = v
            elif num == 4:
                events.append(v)
        for ev in events:
            meta = off = dur = 0
            cat = None
            for num, _, v in _fields(ev):
                if num == 1:
                    meta = v
                    if meta not in wanted:
                        break  # field 1 is serialized first
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
                elif num == 4 and is_device and meta not in cats:
                    k, s = _stat(v, stat_names)
                    if k == "hlo_category":
                        cat = str(s)
            if meta in wanted:
                yield Event(name, line_name, names[meta],
                            cats.get(meta, cat), t0_ns * 1000 + off, dur)


def read_events(path: str, keep_host: bool = True) -> List[Event]:
    """Every device event and every host span of the trace, times in
    picoseconds on the trace's one clock."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: List[Event] = []
    for num, _, v in _fields(buf):
        if num == 1:
            out.extend(_plane(v, keep_host))
    return out
