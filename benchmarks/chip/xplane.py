"""What `jax.profiler.ProfileData` leaves out of an `.xplane.pb`: the stats
of each event's metadata, where a TPU trace keeps what XLA knows of an op,
its name stack among them.

A plain walk over the protobuf wire format of the profiler's `XSpace`
(`tsl/profiler/protobuf/xplane.proto`), with no generated code: the planes
that `keep(plane_name)` selects are decoded, every other plane is skipped
by its length.
"""
from __future__ import annotations

import dataclasses
import struct

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_DOUBLE, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_BYTES, _STAT_REF = range(1, 8)


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict          # line name -> [(metadata id, start ns, duration ns)]
    metadata: dict       # metadata id -> (event name, {stat name: value})


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, value) of one message: an int for a varint or a fixed
    field, a (start, end) pair into `buf` for a length-delimited one."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = struct.unpack_from('<Q', buf, i)[0], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 5:
            val, i = struct.unpack_from('<I', buf, i)[0], i + 4
        else:
            raise ValueError(f'unsupported protobuf wire type {wire}')
        yield num, val


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode('utf-8', 'replace')


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entries(buf, span):
    key, value = 0, None
    for num, val in _fields(buf, *span):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _stat(buf, span, stat_names):
    name, value = None, None
    for num, val in _fields(buf, *span):
        if num == _STAT_META_ID:
            name = stat_names.get(val, str(val))
        elif num == _STAT_DOUBLE:
            value = struct.unpack('<d', struct.pack('<Q', val))[0]
        elif num in (_STAT_UINT, _STAT_INT):
            value = _signed(val) if num == _STAT_INT else val
        elif num == _STAT_STR:
            value = _str(buf, val)
        elif num == _STAT_BYTES:
            value = bytes(buf[val[0]:val[1]])
        elif num == _STAT_REF:
            value = stat_names.get(val, '')
    return name, value


def _plane(buf, span) -> Plane:
    name, lines, metas, stat_metas = '', [], [], {}
    for num, val in _fields(buf, *span):
        if num == _PLANE_NAME:
            name = _str(buf, val)
        elif num == _PLANE_LINES:
            lines.append(val)
        elif num == _PLANE_EVENT_META:
            metas.append(val)
        elif num == _PLANE_STAT_META:
            sid, body = _map_entries(buf, val)
            for n2, v2 in _fields(buf, *body) if body else ():
                if n2 == 2:
                    stat_metas[sid] = _str(buf, v2)
    metadata = {}
    for entry in metas:
        mid, body = _map_entries(buf, entry)
        ev_name, stats = '', {}
        for num, val in _fields(buf, *body) if body else ():
            if num == _META_NAME:
                ev_name = _str(buf, val)
            elif num == _META_STATS:
                k, v = _stat(buf, val, stat_metas)
                stats[k] = v
        metadata[mid] = (ev_name, stats)
    out = {}
    for line in lines:
        lname, t0, events = '', 0, []
        for num, val in _fields(buf, *line):
            if num == _LINE_NAME:
                lname = _str(buf, val)
            elif num == _LINE_TIMESTAMP_NS:
                t0 = _signed(val)
            elif num == _LINE_EVENTS:
                events.append(val)
        decoded = []
        for ev in events:
            mid = off = dur = 0
            for num, val in _fields(buf, *ev):
                if num == _EVENT_META_ID:
                    mid = val
                elif num == _EVENT_OFFSET_PS:
                    off = _signed(val)
                elif num == _EVENT_DURATION_PS:
                    dur = _signed(val)
            decoded.append((mid, t0 + off // 1000, dur // 1000))
        out.setdefault(lname, []).extend(decoded)
    return Plane(name=name, lines=out, metadata=metadata)


def read_planes(path: str, keep) -> list[Plane]:
    """The planes of the `.xplane.pb` at `path` whose name `keep` accepts."""
    with open(path, 'rb') as f:
        buf = memoryview(f.read())
    planes = []
    for num, span in _fields(buf):
        if num != _SPACE_PLANES:
            continue
        name = next((_str(buf, v) for n, v in _fields(buf, *span) if n == _PLANE_NAME), '')
        if keep(name):
            planes.append(_plane(buf, span))
    return planes
