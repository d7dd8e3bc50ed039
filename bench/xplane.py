"""A reader of the profiler's ``.xplane.pb`` (an ``XSpace`` protocol
buffer) that needs nothing but the standard library: the wire format is
decoded by hand for the few messages the trace reduction reads, so that
the event metadata (names, stats, and the compiled programs' HLO) is
available, which ``jax.profiler.ProfileData`` does not expose.

Field numbers follow ``tsl/profiler/protobuf/xplane.proto``."""

from __future__ import annotations

import struct


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) pairs of one message: an int for varints
    and fixed-width values, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, names):
    mid, val = 0, None
    for num, v in fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", struct.pack("<q", v))[0]
        elif num == 3:
            val = v
        elif num == 7:                    # a reference to a stat name
            val = names.get(v, v)
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            val = bytes(v)
    return names.get(mid, str(mid)), val


def _map_entry(buf):
    key = val = None
    for num, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def planes(path: str) -> list:
    """[{"name", "stats", "event_metadata": {id: {"name", "stats"}},
    "lines": [{"name", "events": [(start_ns, end_ns, metadata_id,
    stats)]}]}] of every plane in the file."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = []
    for num, pbuf in fields(data):
        if num != 1:
            continue
        raw = list(fields(pbuf))
        names = {}
        for n, v in raw:
            if n == 5:
                k, sm = _map_entry(v)
                for n2, v2 in fields(sm):
                    if n2 == 2:
                        names[k] = bytes(v2).decode()
        plane = {"name": "", "stats": {}, "event_metadata": {}, "lines": []}
        for n, v in raw:
            if n == 2:
                plane["name"] = bytes(v).decode()
            elif n == 6:
                k, val = _stat(v, names)
                plane["stats"][k] = val
            elif n == 4:
                k, em = _map_entry(v)
                md = {"name": "", "stats": {}}
                for n2, v2 in fields(em):
                    if n2 == 2:
                        md["name"] = bytes(v2).decode("utf-8", "replace")
                    elif n2 == 5:
                        sk, sv = _stat(v2, names)
                        md["stats"][sk] = sv
                plane["event_metadata"][k] = md
            elif n == 3:
                plane["lines"].append(_line(v, names))
        out.append(plane)
    return out


def _line(buf, names):
    line = {"name": "", "events": []}
    ts_ns, evs = 0, []
    for n, v in fields(buf):
        if n == 2:
            line["name"] = bytes(v).decode()
        elif n == 3:
            ts_ns = v
        elif n == 4:
            evs.append(v)
    for ebuf in evs:
        mid = off = dur = 0
        stats = {}
        for n, v in fields(ebuf):
            if n == 1:
                mid = v
            elif n == 2:
                off = v
            elif n == 3:
                dur = v
            elif n == 4:
                k, val = _stat(v, names)
                stats[k] = val
        start = ts_ns + off / 1000.0
        line["events"].append((start, start + dur / 1000.0, mid, stats))
    return line


def hlo_text(hlo_proto: bytes) -> str:
    """Text of the compiled module in an ``HloProto`` (its field 1 is the
    ``HloModuleProto``), with each instruction's metadata."""
    from jax._src.lib import xla_client

    module = next(bytes(v) for n, v in fields(memoryview(hlo_proto))
                  if n == 1)
    mod = xla_client._xla.HloModule.from_serialized_hlo_module_proto(module)
    return mod.to_string()
