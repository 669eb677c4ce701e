"""What ``jax.profiler.ProfileData`` leaves out of a ``*.xplane.pb``: the
stats a device plane keeps once per *kind* of op (its ``XEventMetadata``)
rather than on each executed event -- ``tf_op`` (the op's ``jax.named_scope``
path), ``hlo_category``, ``program_id``, ``source``, ``flops``,
``bytes_accessed``. ``lib/xplane.py`` reads events through ``ProfileData``,
whose ``event.stats`` holds the per-event stats only, so a reader that wants
an op's scope joins on the op's name through this module.

The file is a protocol buffer (``tsl/profiler/protobuf/xplane.proto``); the
few fields needed are read straight off the wire format, and the lines of
events, which are nearly all of the file, are skipped by their length:

    XSpace.planes = 1
    XPlane: name = 2, lines = 3 (skipped), event_metadata = 4, stat_metadata = 5
      map entries: key = 1, value = 2
    XEventMetadata: name = 2, stats = 5        XStatMetadata: name = 2
    XStat: metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5,
           bytes = 6, ref = 7 (the id of a stat metadata whose name is the value)
"""

from __future__ import annotations

import re
import struct
from typing import Dict, Iterator, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for
    varint and fixed types, a ``memoryview`` for length-delimited ones."""
    view = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = view[i:i + size]
            i += size
        elif wire == 1:
            value = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            value = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _map_entry(entry) -> Tuple[int, bytes]:
    key, value = 0, b""
    for field, _, v in _fields(bytes(entry)):
        if field == 1:
            key = v
        elif field == 2:
            value = bytes(v)
    return key, value


def _name(message: bytes) -> str:
    for field, wire, v in _fields(message):
        if field == 2 and wire == 2:
            return bytes(v).decode("utf-8", "replace")
    return ""


def _stat(stat: bytes):
    """``(metadata id, value, is_ref)`` of one XStat."""
    key, value, ref = 0, None, False
    for field, wire, v in _fields(stat):
        if field == 1:
            key = v
        elif field in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif field == 7:
            value, ref = v, True
        elif field == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif field in (3, 4):
            value = v
    return key, value, ref


def op_metadata(path: str, wanted: Sequence[str] = ("tf_op",)
                ) -> Dict[int, Dict[str, Dict[str, object]]]:
    """``{device: {op name: {stat: value}}}`` for the stats ``wanted`` of
    every kind of op on each ``/device:TPU:<n>`` plane of the file. The op
    name is the one ``ProfileData`` gives the op's events. Two kinds of op
    with one name (the same instruction text in two programs) keep the
    first's stats."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[int, Dict[str, Dict[str, object]]] = {}
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        plane = bytes(plane)
        name, events, stats = "", [], {}
        for pf, pw, v in _fields(plane):
            if pw != 2:
                continue
            if pf == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                key, meta = _map_entry(v)
                stats[key] = _name(meta)
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)), {})
        for entry in events:
            _, meta = _map_entry(entry)
            op_name, found = "", {}
            for ef, ew, v in _fields(meta):
                if ef == 2 and ew == 2:
                    op_name = bytes(v).decode("utf-8", "replace")
                elif ef == 5 and ew == 2:
                    key, value, ref = _stat(bytes(v))
                    if stats.get(key) in wanted:
                        found[stats[key]] = stats.get(value, "") if ref \
                            else value
            if op_name and found:
                ops.setdefault(op_name, found)
    return out
