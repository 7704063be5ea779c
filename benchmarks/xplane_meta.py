"""What ``jax.profiler.ProfileData`` hides of an ``.xplane.pb``: the
stats on each event's *metadata*. On a chip's ``XLA Ops`` line that is
where the compiler's facts about an operation sit: ``tf_op`` (the jax
op path, ``jit(fm_train_step)/jvp(expand)/gather``), ``source``
(file:line), ``hlo_category``, ``bytes_accessed``, ``flops``.
``ProfileData`` gives an event's own stats (``device_offset_ps``...)
and its name, and the name is the metadata's: a plane's builder keys
its event metadata by name, so name -> stats is the whole relation.

A small reader of the protobuf wire format, and no ``xplane_pb2``: the
one importable here is tensorflow's, which takes 8 s to import and
loads libtpu a second time in the process that holds the chip.

Fields read (tsl/profiler/protobuf/xplane.proto): XSpace.planes=1;
XPlane.name=2, event_metadata=4, stat_metadata=5 (maps: key=1,
value=2); XEventMetadata.name=2, display_name=4, stats=5;
XStatMetadata.name=2; XStat.metadata_id=1, double_value=2,
uint64_value=3, int64_value=4, str_value=5, bytes_value=6,
ref_value=7 (the id of a stat metadata whose name is the value)."""

from __future__ import annotations

import struct
from typing import Dict, Iterator, Tuple, Union

Value = Union[int, float, str, bytes]


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Union[int, bytes]]]:
    """(field number, wire type, value) of one message (bytes or a
    memoryview of them); a value is an int (varint, fixed) or the
    bytes, sliced from ``buf``, of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an "
                             "xplane file, or a torn one")
        if i > n:
            raise ValueError("a field runs past the end: a torn file")
        yield field, wire, v


def _map_entry(buf) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Value]:
    name, value = "", 0
    for f, _, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = str(v, "utf-8", "replace")
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf) -> Tuple[str, Dict[str, Dict[str, Value]]]:
    name, events, stat_names = "", [], {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = str(v, "utf-8", "replace")
        elif f == 4:
            events.append(_map_entry(v)[1])
        elif f == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (str(x, "utf-8", "replace")
                 for g, _, x in _fields(meta) if g == 2), str(key))
    out: Dict[str, Dict[str, Value]] = {}
    for meta in events:
        ev_name, stats = "", {}
        for f, _, v in _fields(meta):
            if f == 2:
                ev_name = str(v, "utf-8", "replace")
            elif f == 4:
                stats["display_name"] = str(v, "utf-8", "replace")
            elif f == 5:
                k, val = _stat(v, stat_names)
                stats[k] = val
        out.setdefault(ev_name, stats)
    return name, out


def read(path: str) -> Dict[str, Dict[str, Dict[str, Value]]]:
    """plane name -> event metadata name -> {stat name: value}."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())     # nested messages without copies
    return dict(_plane(v) for f, _, v in _fields(buf) if f == 1)


def _put_varint(out: bytearray, v: int) -> None:
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)


def _put(out: bytearray, field: int, wire: int, v) -> None:
    _put_varint(out, field << 3 | wire)
    if wire == 0:
        _put_varint(out, v)
    elif wire == 2:
        _put_varint(out, len(v))
        out += v
    else:
        out += v.to_bytes(8 if wire == 1 else 4, "little")


def cut(src: str, dst: str, span_prefixes=("train/", "obs/", "pipeline/",
                                           "predict/", "fetch/")) -> None:
    """A recorded trace cut to what the tests read (``testdata/``):
    the ``/device:`` planes whole, and of ``/host:CPU`` only the thread
    lines that hold one of the program's spans (XPlane.lines=3;
    XLine.name=2, events=4; XEvent.metadata_id=1)."""
    with open(src, "rb") as fh:
        buf = memoryview(fh.read())
    out = bytearray()
    for f, w, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((str(v, "utf-8") for g, _, v in fields if g == 2), "")
        if name.startswith("/device:"):
            _put(out, 1, 2, plane)
            continue
        if not name.startswith("/host:CPU"):
            continue
        spans = set()
        for g, _, v in fields:
            if g == 4:
                key, meta = _map_entry(v)
                ev = next((str(x, "utf-8", "replace")
                           for h, _, x in _fields(meta) if h == 2), "")
                if ev.startswith(tuple(span_prefixes)):
                    spans.add(key)
        kept = bytearray()
        for g, w2, v in fields:
            if g == 3 and not any(
                    h == 4 and next((y for i, _, y in _fields(x)
                                     if i == 1), None) in spans
                    for h, _, x in _fields(v)):
                continue
            _put(kept, g, w2, v)
        _put(out, 1, 2, bytes(kept))
    with open(dst, "wb") as fh:
        fh.write(bytes(out))


def describe(path: str, limit: int = 12) -> str:
    """The first metadata of every plane, for a look by hand."""
    out = []
    for plane, events in read(path).items():
        out.append(f"PLANE {plane}: {len(events)} event metadata")
        for name, stats in list(events.items())[:limit]:
            out.append(f"  {name[:90]}")
            for k, v in stats.items():
                out.append(f"      {k} = {str(v)[:140]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    if sys.argv[1] == "cut":
        cut(sys.argv[2], sys.argv[3])
    else:
        print(describe(sys.argv[1], int(sys.argv[2])
                       if len(sys.argv) > 2 else 12))
