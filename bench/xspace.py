"""Read each device op's ``tf_op`` out of a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives every event's name, times and stats, but
not the stats of an event's *metadata*, which is where a device op's
``tf_op`` (its HLO ``op_name``, named scopes included) is kept. This module
reads them from the serialized ``XSpace`` with the standard library alone,
following the protobuf wire format and these fields of
``tsl/profiler/protobuf/xplane.proto``::

    XSpace         planes = 1 (XPlane)
    XPlane         name = 2; event_metadata = 4 (map<int64, XEventMetadata>);
                   stat_metadata = 5 (map<int64, XStatMetadata>)
    XEventMetadata name = 2; stats = 5 (XStat)
    XStatMetadata  id = 1; name = 2
    XStat          metadata_id = 1; str_value = 5; ref_value = 7

A map entry is a message with the key in field 1 and the value in field 2.
A ``ref_value`` names the ``XStatMetadata`` whose ``name`` is the string.
"""
from __future__ import annotations

import gzip

TF_OP = "tf_op"
_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of each field of one message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _LEN:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == _I64:
            value, i = buf[i:i + 8], i + 8
        elif wire == _I32:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry):
    """The value message of a map entry."""
    for field, value in _fields(entry):
        if field == 2:
            return value
    return b""


def _plane_tf_ops(plane) -> tuple:
    name, event_meta, stat_names = "", [], {}
    for field, value in _fields(plane):
        if field == 2:
            name = bytes(value).decode()
        elif field == 4:
            event_meta.append(_map_value(value))
        elif field == 5:
            meta = dict(_fields(_map_value(value)))
            stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
    tf_op_ids = {i for i, n in stat_names.items() if n == TF_OP}
    ops = {}
    for meta in event_meta:
        op_name, tf_op = "", None
        for field, value in _fields(meta):
            if field == 2:
                op_name = bytes(value).decode()
            elif field == 5:
                stat = dict(_fields(value))
                if stat.get(1, 0) not in tf_op_ids:
                    continue
                if 5 in stat:
                    tf_op = bytes(stat[5]).decode()
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7], "")
        if tf_op is not None:
            ops[op_name] = tf_op
    return name, ops


def read_bytes(path: str) -> bytes:
    """The serialized XSpace of a ``.xplane.pb`` file or of its gzip."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def tf_ops(data: bytes, plane_prefix: str = "/device:") -> dict:
    """{plane name: {event metadata name: tf_op}} for each plane whose name
    starts with ``plane_prefix``; a device op event's name is its metadata
    name."""
    out = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        name, ops = _plane_tf_ops(plane)
        if name.startswith(plane_prefix):
            out[name] = ops
    return out
