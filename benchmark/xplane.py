"""Read the event metadata of a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives each event its name, start, duration
and its own stats, but not the stats of the event's metadata, where the
TPU profiler keeps what an operation was traced from: ``tf_op`` (the
HLO ``op_name``, with its ``jax.named_scope`` path) and ``program_id``.
This reads them from the protobuf wire format directly, with no
protobuf or TensorFlow package, and decodes only what it needs: each
plane's name, its ``event_metadata`` (id, name and stats) and its
``stat_metadata`` names. Lines and events are skipped unread.

The messages, from ``tsl/profiler/protobuf/xplane.proto``:
``XSpace.planes`` = 1; ``XPlane`` name = 2, lines = 3, event_metadata =
4 and stat_metadata = 5 (maps: key = 1, value = 2); ``XEventMetadata``
id = 1, name = 2, stats = 5; ``XStatMetadata`` id = 1, name = 2;
``XStat`` metadata_id = 1, then one of double = 2, uint64 = 3, int64 =
4, str = 5, bytes = 6, ref = 7 (the id of a stat metadata whose name is
the value).
"""

from __future__ import annotations

import struct

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of each field of one message:
    an int for varints and fixed-width fields, a ``memoryview`` for
    length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == VARINT:
            val, i = _varint(buf, i)
        elif wt == BYTES:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == FIXED64:
            val, i = buf[i:i + 8], i + 8
        elif wt == FIXED32:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, _, val in fields(buf):
        if num == 1:
            key = _signed(val)
        elif num == 2:
            value = val
    return key, value


def _stat(buf, stat_names: dict) -> tuple[int, object]:
    mid, value = 0, None
    for num, _, val in fields(buf):
        if num == 1:
            mid = val
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num in (3, 4):
            value = val if num == 3 else _signed(val)
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value = stat_names.get(val, val)
    return mid, value


def read_planes(data: bytes) -> list[dict]:
    """One dict per plane, in file order: ``name`` and ``ops``, the event
    metadata as ``{metadata id: {"name": ..., "stats": {stat name:
    value}}}``."""
    buf = memoryview(data)
    out = []
    for num, _, plane in fields(buf):
        if num != 1:
            continue
        name, stat_names, raw_events = "", {}, []
        for pnum, _, val in fields(plane):
            if pnum == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pnum == 4:
                raw_events.append(_map_entry(val))
            elif pnum == 5:
                sid, meta = _map_entry(val)
                for mnum, _, mval in fields(meta):
                    if mnum == 2:
                        stat_names[sid] = bytes(mval).decode(
                            "utf-8", "replace")
        ops = {}
        for eid, meta in raw_events:
            ename, stats = "", {}
            for mnum, _, mval in fields(meta):
                if mnum == 2:
                    ename = bytes(mval).decode("utf-8", "replace")
                elif mnum == 5:
                    sid, value = _stat(mval, stat_names)
                    stats[stat_names.get(sid, str(sid))] = value
            ops[eid] = {"name": ename, "stats": stats}
        out.append({"name": name, "ops": ops})
    return out


def read_file(path: str) -> list[dict]:
    with open(path, "rb") as f:
        return read_planes(f.read())
