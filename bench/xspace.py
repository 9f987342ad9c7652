"""A reader of the profiler's `.xplane.pb` files that needs nothing but
Python: the few fields of the `XSpace` protobuf that the trace reduction
uses, decoded from the wire format.

`jax.profiler.ProfileData` reads the same file but shows only an event's
own statistics; the statistics of an event's *metadata*, where the device
trace keeps each XLA op's `tf_op` (its `jax.named_scope` path), are not
exposed there.  The schema read here is tsl's `xplane.proto`:

    XSpace          planes = 1
    XPlane          name = 2, lines = 3, event_metadata = 4 (map),
                    stat_metadata = 5 (map)
    XLine           name = 2, timestamp_ns = 3, events = 4
    XEvent          metadata_id = 1, offset_ps = 2, duration_ps = 3,
                    stats = 4
    XStat           metadata_id = 1, double = 2, uint64 = 3, int64 = 4,
                    str = 5, bytes = 6, ref = 7
    XEventMetadata  id = 1, name = 2, stats = 5
    XStatMetadata   id = 1, name = 2

Times are kept as `ProfileData` gives them: an event starts at the line's
`timestamp_ns` plus its whole nanoseconds of offset, and lasts its whole
nanoseconds of duration.
"""
from __future__ import annotations

import dataclasses
import struct
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

_VARINT, _FIXED64, _LEN, _FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message in buf[i:end]: an int for a
    varint, (start, end) for a length-delimited field, bytes for a fixed
    one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _LEN:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == _FIXED64:
            value = buf[i:i + 8]
            i += 8
        elif wire == _FIXED32:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire} in an XSpace")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


class Event:
    """One event: its metadata's name, start and end, the statistics of
    its metadata, and its own statistics (decoded when first read)."""
    __slots__ = ("name", "start_ns", "end_ns", "metadata", "_plane",
                 "_stats")

    def __init__(self, name, start_ns, end_ns, metadata, plane, stats):
        self.name, self.start_ns, self.end_ns = name, start_ns, end_ns
        self.metadata = metadata
        self._plane, self._stats = plane, stats

    @property
    def stats(self) -> Dict[str, object]:
        if isinstance(self._stats, list):
            self._stats = dict(self._plane._stat(v) for v in self._stats)
        return self._stats


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


class _PlaneReader:
    """Decodes one XPlane; statistics are decoded when first asked for."""

    def __init__(self, buf: bytes, i: int, end: int):
        self.buf = buf
        self.name = ""
        self._lines: List[Tuple[int, int]] = []
        self._event_md: Dict[int, Tuple[int, int]] = {}
        self._stat_names: Dict[int, str] = {}
        for field, value in _fields(buf, i, end):
            if field == 2:
                self.name = buf[value[0]:value[1]].decode()
            elif field == 3:
                self._lines.append(value)
            elif field in (4, 5):
                key, entry = 0, None
                for f, v in _fields(buf, *value):
                    if f == 1:
                        key = v
                    elif f == 2:
                        entry = v
                if entry is None:
                    continue
                if field == 4:
                    self._event_md[key] = entry
                else:
                    self._stat_names[key] = self._name_of(entry)
        self._md_cache: Dict[int, Tuple[str, Dict[str, object]]] = {}

    def _name_of(self, span) -> str:
        for f, v in _fields(self.buf, *span):
            if f == 2:
                return self.buf[v[0]:v[1]].decode(errors="replace")
        return ""

    def _stat(self, span) -> Tuple[str, object]:
        buf, mid, value = self.buf, 0, None
        for f, v in _fields(buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                value = struct.unpack("<d", v)[0]
            elif f == 3:
                value = v
            elif f == 4:
                value = _signed(v)
            elif f == 5:
                value = buf[v[0]:v[1]].decode(errors="replace")
            elif f == 6:
                value = bytes(buf[v[0]:v[1]])
            elif f == 7:
                value = self._stat_names.get(v, "")
        return self._stat_names.get(mid, ""), value

    def _metadata(self, mid: int) -> Tuple[str, Dict[str, object]]:
        if mid not in self._md_cache:
            name, stats = "", {}
            span = self._event_md.get(mid)
            if span is not None:
                for f, v in _fields(self.buf, *span):
                    if f == 2:
                        name = self.buf[v[0]:v[1]].decode(errors="replace")
                    elif f == 5:
                        k, val = self._stat(v)
                        stats[k] = val
            self._md_cache[mid] = (name, stats)
        return self._md_cache[mid]

    def plane(self) -> Plane:
        buf, lines = self.buf, []
        for i, end in self._lines:
            name, ts, events = "", 0, []
            for f, v in _fields(buf, i, end):
                if f == 2:
                    name = buf[v[0]:v[1]].decode(errors="replace")
                elif f == 3:
                    ts = _signed(v)
                elif f == 4:
                    events.append(v)
            lines.append(Line(name, [self._event(ts, *e) for e in events]))
        return Plane(self.name, lines)

    def _event(self, ts: int, i: int, end: int) -> Event:
        mid = offset = duration = 0
        stats = []
        for f, v in _fields(self.buf, i, end):
            if f == 1:
                mid = v
            elif f == 2:
                offset = v
            elif f == 3:
                duration = v
            elif f == 4:
                stats.append(v)
        name, metadata = self._metadata(mid)
        start = float(ts + offset // 1000)
        return Event(name, start, start + duration // 1000, metadata, self,
                     stats)


def read(path: Path) -> List[Plane]:
    """Every plane of an `.xplane.pb` file, with its lines and events."""
    buf = Path(path).read_bytes()
    return [_PlaneReader(buf, *v).plane()
            for f, v in _fields(buf, 0, len(buf)) if f == 1]
