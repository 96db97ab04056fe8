"""Just enough of the protocol-buffer wire format to read, from an
``.xplane.pb``, what ``jax.profiler.ProfileData`` does not show: each
plane's table of event metadata (display name, scope path ``tf_op``,
``hlo_category``, ``source``).

Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:
XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5
(maps: key = 1, value = 2); XEventMetadata.name = 2, .display_name = 4,
.stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
.ref_value = 7.
"""
from __future__ import annotations

KEEP = ("tf_op", "hlo_category", "source")


def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return x, i


def fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value comes back as a view of its bytes, unparsed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError("wire type %d" % kind)
        yield number, kind, value


def _map_entry(buf):
    key = value = None
    for number, _kind, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def event_metadata(path: str) -> dict:
    """{plane name: {event name: {"display_name", "tf_op", ...}}}."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = {}
    for number, _kind, plane in fields(data):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, _k, v in fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(_map_entry(v)[1])
            elif f == 5:
                key, meta = _map_entry(v)
                for mf, _mk, mv in fields(meta):
                    if mf == 2:
                        stat_names[key] = _text(mv)
        table = {}
        for meta in events:
            if meta is None:
                continue
            rec, ev_name = {}, ""
            for f, _k, v in fields(meta):
                if f == 2:
                    ev_name = _text(v)
                elif f == 4:
                    rec["display_name"] = _text(v)
                elif f == 5:
                    stat = {sf: sv for sf, _sk, sv in fields(v)}
                    key = stat_names.get(stat.get(1))
                    if key in KEEP:
                        if 5 in stat:
                            rec[key] = _text(stat[5])
                        elif 7 in stat:
                            rec[key] = stat_names.get(stat[7], "")
            table[ev_name] = rec
        out[name] = table
    return out
