"""A reader of the profiler's ``XSpace`` protobuf that keeps what
``jax._src.profiler.ProfileData`` drops: the stats on *event metadata*,
where the TPU profiler puts each operation's ``tf_op`` (its JAX name
stack, e.g. ``jit(prefill_step)/while/body/attention/dot_general:``).

It decodes the wire format directly (no protobuf dependency), the fields
of ``tsl/profiler/protobuf/xplane.proto`` that it needs::

    XSpace         planes=1
    XPlane         name=2 lines=3 event_metadata=4 (map) stat_metadata=5 (map)
    XLine          name=2 timestamp_ns=3 events=4
    XEvent         metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XStat          metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
    XEventMetadata id=1 name=2 stats=5
    XStatMetadata  id=1 name=2

:func:`read` returns plain tuples; times are nanoseconds on the
profiler's clock, as ``ProfileData`` gives them.
"""
from __future__ import annotations

import gzip
import struct
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i + 1
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, wire type, value) of one message: the value is an int
    for varints and fixed widths, (start, end) for length-delimited."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == 1:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wt == 5:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"xspace: wire type {wt} at byte {i}")
        yield key >> 3, wt, v


def _skip(buf: bytes, i: int) -> int:
    """The index after the field that starts at ``i``."""
    key, j = _varint(buf, i)
    wt = key & 7
    if wt == 0:
        return _varint(buf, j)[1]
    if wt == 2:
        n, j = _varint(buf, j)
        return j + n
    return j + (8 if wt == 1 else 4)


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span, stat_names: Dict[int, str]):
    """(name, value) of one XStat; a ref value is a stat metadata name."""
    name, value = None, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", struct.pack("<q", v))[0]
        elif f == 3:
            value = v
        elif f == 7:
            value = stat_names.get(v, v)
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _str(buf, v)
    return name, value


@dataclass
class Line:
    name: str
    # (metadata id, start_ns, end_ns, [stat spans]) of each event
    events: List[tuple] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line]
    meta: Dict[int, Tuple[str, Dict[str, object]]]   # id -> (name, stats)
    buf: bytes = b""
    stat_names: Dict[int, str] = field(default_factory=dict)

    def event_name(self, ev) -> str:
        return self.meta.get(ev[0], ("", {}))[0]

    def event_stats(self, ev) -> Dict[str, object]:
        return dict(_stat(self.buf, s, self.stat_names) for s in ev[3])


def _metadata(buf: bytes, span, stat_names) -> Tuple[int, str, List]:
    mid, name, stats = 0, "", []
    for f, _, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            name = _str(buf, v)
        elif f == 5:
            stats.append(v)
    return mid, name, stats


def _line(buf: bytes, span, keep: Callable[[str], bool],
          want_stats: Callable[[int], bool]) -> Optional[Line]:
    """The line at ``span``, its events decoded, or None where ``keep``
    refuses its name (then no event is decoded)."""
    name, ts, events = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            ts = v
        elif f == 4:
            events.append(v)
    if not keep(name):
        return None
    out = Line(name)
    append = out.events.append
    for a, b in events:
        # the hot loop: XEvent's fields inline (one-byte keys), the rest
        # through _fields
        mid = off = dur = 0
        stats = []
        i = a
        while i < b:
            key = buf[i]
            if key == 0x08:
                mid, i = _varint(buf, i + 1)
            elif key == 0x10:
                off, i = _varint(buf, i + 1)
            elif key == 0x18:
                dur, i = _varint(buf, i + 1)
            elif key == 0x22:
                n, i = _varint(buf, i + 1)
                stats.append((i, i + n))
                i += n
            else:
                i = _skip(buf, i)
        start = ts + off / 1e3
        append((mid, start, start + dur / 1e3,
                stats if stats and want_stats(mid) else []))
    return out


def read(path: str, keep_plane: Callable[[str], bool],
         keep_line: Callable[[str, str], bool] = lambda p, l: True,
         event_stats: Optional[Callable[[str], bool]] = None) -> List[Plane]:
    """The planes whose name ``keep_plane`` accepts, with the lines
    ``keep_line(plane, line)`` accepts.  Event metadata carries its stats
    decoded; an event's own stats are kept (as spans, decoded by
    :meth:`Plane.event_stats`) where ``event_stats(metadata name)``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    planes = []
    for f, _, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, metas, stat_meta = "", [], [], {}
        for pf, _, v in _fields(buf, *span):
            if pf == 2:
                name = _str(buf, v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                for mf, _, mv in _fields(buf, *v):
                    if mf == 2:
                        sid, sname = 0, ""
                        for sf, _, sv in _fields(buf, *mv):
                            if sf == 1:
                                sid = sv
                            elif sf == 2:
                                sname = _str(buf, sv)
                        stat_meta[sid] = sname
        if not keep_plane(name):
            continue
        meta = {}
        for entry in metas:
            for mf, _, mv in _fields(buf, *entry):
                if mf == 2:
                    mid, mname, stats = _metadata(buf, mv, stat_meta)
                    meta[mid] = (mname, dict(_stat(buf, s, stat_meta)
                                             for s in stats))
        wanted = {mid for mid, (n, _) in meta.items()
                  if event_stats is not None and event_stats(n)}
        kept = [_line(buf, v, lambda line: keep_line(name, line),
                      wanted.__contains__) for v in lines]
        planes.append(Plane(name, [x for x in kept if x is not None], meta,
                            buf, stat_meta))
    return planes
