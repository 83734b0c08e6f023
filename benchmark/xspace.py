"""The profiler's ``.xplane.pb`` read as what it is: protobuf wire format.

``jax.profiler.ProfileData`` gives an event's own stats and not those of
its event metadata, and that is where the TPU runtime keeps what an HLO
operation is: its ``op_name`` path with the program's scopes in it.  So
this module decodes the file itself (varints and length-delimited fields;
TensorFlow is not imported) by the field numbers of
``tsl/profiler/protobuf/xplane.proto``:

    XSpace          planes = 1
    XPlane          name = 2, lines = 3, event_metadata = 4 (map),
                    stat_metadata = 5 (map), stats = 6
    XLine           name = 2, timestamp_ns = 3, events = 4
    XEvent          metadata_id = 1, offset_ps = 2, duration_ps = 3, stats = 4
    XStat           metadata_id = 1, double = 2, uint64 = 3, int64 = 4,
                    str = 5, bytes = 6, ref = 7 (a stat metadata's name)
    XEventMetadata  id = 1, name = 2, display_name = 4, stats = 5
    XStatMetadata   id = 1, name = 2
    map entry       key = 1, value = 2

``fields`` and ``encode`` are the wire format alone, both ways (the tests
build files with them, and ``trim`` cuts a recorded trace to size);
``read_planes`` is the reading of a trace that ``benchmark/scopes.py``
reduces.

    python -m benchmark.xspace <file.xplane.pb>            # what a trace holds
    python -m benchmark.xspace <in> <out> <steps>          # cut to <steps> steps
"""

from __future__ import annotations

import struct
import sys

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


# -- wire format -------------------------------------------------------------

def fields(buf) -> list:
    """``[(field number, wire type, value)]`` of one message: an int for a
    varint or a fixed field, ``bytes`` for a length-delimited one."""
    buf = bytes(buf)
    out, at, end = [], 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == VARINT:
            value, at = _varint(buf, at)
        elif kind == BYTES:
            size, at = _varint(buf, at)
            value = buf[at:at + size]
            if len(value) != size:
                raise ValueError("a field runs past the end of its message")
            at += size
        elif kind == FIXED64:
            value = int.from_bytes(buf[at:at + 8], "little")
            at += 8
        elif kind == FIXED32:
            value = int.from_bytes(buf[at:at + 4], "little")
            at += 4
        else:
            raise ValueError(f"wire type {kind} at byte {at}: not a "
                             f"protobuf message")
        out.append((number, kind, value))
    return out


def _varint(buf: bytes, at: int) -> tuple:
    value = shift = 0
    while True:
        if at >= len(buf):
            raise ValueError("a varint runs past the end of its message")
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def encode(message) -> bytes:
    """The inverse of ``fields``.  A length-delimited value may itself be
    a list of fields."""
    out = bytearray()
    for number, kind, value in message:
        out += _encode_varint(number << 3 | kind)
        if kind == VARINT:
            out += _encode_varint(value)
        elif kind == BYTES:
            if isinstance(value, list):
                value = encode(value)
            elif isinstance(value, str):
                value = value.encode()
            out += _encode_varint(len(value)) + value
        else:
            out += value.to_bytes(8 if kind == FIXED64 else 4, "little")
    return bytes(out)


def _encode_varint(value: int) -> bytes:
    value &= (1 << 64) - 1             # negative int64: ten bytes
    out = bytearray()
    while value > 0x7F:
        out.append(value & 0x7F | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _first(message, number, default=None):
    for n, _, value in message:
        if n == number:
            return value
    return default


def _text(message, number) -> str:
    return _first(message, number, b"").decode(errors="replace")


def _map(plane, number) -> dict:
    """``{key: the value message's fields}`` of a plane's map ``number``
    (event metadata is 4, stat metadata 5), keyed by the value's own id."""
    values = (fields(_first(fields(entry), 2, b""))
              for n, _, entry in plane if n == number)
    return {_first(value, 1, 0): value for value in values}


# -- XSpace ------------------------------------------------------------------

def _stats(message, number: int, stat_names: dict) -> dict:
    """``{stat name: value}`` of the XStat fields ``number`` of an event
    or of an event's metadata."""
    out = {}
    for n, _, raw in message:
        if n != number:
            continue
        name = value = None
        for field, kind, v in fields(raw):
            if field == 1:
                name = stat_names.get(v, str(v))
            elif field == 2:
                value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
            elif field == 4:
                value = _signed(v)
            elif field in (5, 6):
                value = v.decode(errors="replace")
            elif field == 7:
                value = stat_names.get(v, str(v))
            elif field == 3:
                value = v
        out[name] = value
    return out


def read_planes(path: str, want_line=lambda name: True) -> list:
    """``[{"name", "lines": {line name: [event]}}]``, an event being
    ``{"name", "display_name", "start_s", "end_s", "stats"}``: ``stats``
    holds the event metadata's stats with the event's own laid over them.
    Lines that ``want_line`` refuses are skipped undecoded."""
    with open(path, "rb") as f:
        space = fields(f.read())
    planes = []
    for number, _, raw in space:
        if number != 1:
            continue
        plane = fields(raw)
        stat_names = {key: _text(meta, 2)
                      for key, meta in _map(plane, 5).items()}
        metadata = {key: (_text(meta, 2), _text(meta, 4),
                          _stats(meta, 5, stat_names))
                    for key, meta in _map(plane, 4).items()}
        lines = {}
        for n, _, raw_line in plane:
            if n != 3:
                continue
            line = fields(raw_line)
            line_name = _text(line, 2)
            if not want_line(line_name):
                continue
            origin_ns = _signed(_first(line, 3, 0))
            events = lines.setdefault(line_name, [])
            for m, _, raw_event in line:
                if m != 4:
                    continue
                event = fields(raw_event)
                name, display, meta_stats = metadata.get(
                    _first(event, 1, 0), ("", "", {}))
                start = (origin_ns * 1000 + _signed(_first(event, 2, 0))
                         ) * 1e-12
                events.append({
                    "name": name, "display_name": display,
                    "start_s": start,
                    "end_s": start + _first(event, 3, 0) * 1e-12,
                    "stats": {**meta_stats, **_stats(event, 4, stat_names)},
                })
        planes.append({"name": _text(plane, 2), "lines": lines})
    return planes


# -- cutting a recorded trace to size ----------------------------------------

def trim(path: str, out_path: str, steps: int, keep_lines, module_line: str,
         host_events=(), drop_stats=()) -> None:
    """Write ``path`` again with, of every device plane (one that has a
    line ``module_line``), the lines ``keep_lines`` cut to the first
    ``steps`` events of ``module_line``'s most frequent program; of every
    other plane, the events named in ``host_events`` inside that time; and
    only the event metadata those events use, less its stats named in
    ``drop_stats``.  Stat metadata is kept whole."""
    with open(path, "rb") as f:
        space = fields(f.read())
    windows, planes = [], []
    for number, kind, raw in space:
        if number != 1:
            continue
        plane = fields(raw)
        names = {key: _text(meta, 2) for key, meta in _map(plane, 4).items()}
        dropped = {key for key, meta in _map(plane, 5).items()
                   if _text(meta, 2) in drop_stats}
        modules = [fields(raw_line) for n, _, raw_line in plane
                   if n == 3 and _text(fields(raw_line), 2) == module_line]
        planes.append((plane, names, dropped, bool(modules)))
        windows += [_first_runs(line, names, steps) for line in modules]
    if not windows:
        raise ValueError(f"no plane of {path} has a line {module_line!r}")
    first = min(w[0] for w in windows)
    last = max(w[1] for w in windows)

    out = []
    for plane, names, dropped, on_device in planes:
        kept, used = [], set()
        for n, kind, value in plane:
            if n != 3:
                kept.append((n, kind, value))
                continue
            line = fields(value)
            if on_device and _text(line, 2) not in keep_lines:
                continue
            origin_ps = _signed(_first(line, 3, 0)) * 1000
            new_line = []
            for m, k, v in line:
                if m == 4:
                    event = fields(v)
                    start = origin_ps + _signed(_first(event, 2, 0))
                    inside = (first <= start
                              and start + _first(event, 3, 0) <= last)
                    named = on_device or names.get(
                        _first(event, 1, 0)) in host_events
                    if not (inside and named):
                        continue
                    used.add(_first(event, 1, 0))
                new_line.append((m, k, v))
            if any(m == 4 for m, _, _ in new_line):
                kept.append((n, kind, new_line))
        if not used:
            continue
        kept = [(n, kind, _without_stats(value, dropped) if n == 4
                 else value) for n, kind, value in kept
                if n != 4 or _first(fields(value), 1, 0) in used]
        out.append((1, BYTES, kept))
    with open(out_path, "wb") as f:
        f.write(encode(out))


def _without_stats(entry: bytes, dropped: set) -> list:
    """An ``event_metadata`` map entry less the stats whose metadata id is
    in ``dropped``."""
    return [(n, kind, [f for f in fields(value) if not (
        f[0] == 5 and _first(fields(f[2]), 1) in dropped)]
        if n == 2 else value) for n, kind, value in fields(entry)]


def _first_runs(line, names: dict, steps: int) -> tuple:
    """(start_ps, end_ps) from the first to the ``steps``-th execution of
    the program that ``line`` (an ``XLA Modules`` line) shows most often."""
    origin_ps = _signed(_first(line, 3, 0)) * 1000
    runs = {}
    for n, _, raw in line:
        if n == 4:
            event = fields(raw)
            start = origin_ps + _signed(_first(event, 2, 0))
            runs.setdefault(names.get(_first(event, 1, 0)), []).append(
                (start, start + _first(event, 3, 0)))
    most = sorted(max(runs.values(), key=len))[:steps]
    return most[0][0], most[-1][1]


def describe(path: str) -> None:
    for plane in read_planes(path):
        print("PLANE", plane["name"])
        for name, events in plane["lines"].items():
            print(f"  LINE {name!r}: {len(events)} events")
            for event in events[:3]:
                print(f"    {event['name'][:100]!r} display="
                      f"{event['display_name'][:60]!r} "
                      f"start_s={event['start_s']:.9f} "
                      f"end_s={event['end_s']:.9f}")
                for key, value in event["stats"].items():
                    print(f"      {key} = {str(value)[:160]!r}")


if __name__ == "__main__":
    if len(sys.argv) == 2:
        describe(sys.argv[1])
    else:
        from benchmark import trace

        trim(sys.argv[1], sys.argv[2], int(sys.argv[3]),
             keep_lines=set(trace.LINES), module_line="XLA Modules",
             host_events=("dispatch", "wait_loss"),
             drop_stats=("source", "source_stack",
                         "memory_access_breakdown"))
