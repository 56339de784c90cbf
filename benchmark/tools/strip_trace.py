#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to what the reduction reads, so a
small trace can live in git: keeps the ``/device:*`` and ``/host:CPU``
planes, drops every event's stats, keeps at most ``--max-ops`` events
of each ``XLA Ops`` line and only the host events named in ``--keep``
(default: the spans the self-test uses), and drops metadata nothing
refers to. Works on the protobuf wire format directly (XSpace.planes=1;
XPlane.name=2 .lines=3 .event_metadata=4; XLine.name=2 .events=4;
XEvent.metadata_id=1 .stats=4; XEventMetadata.id=1 .name=2), because no
xplane_pb2 is installed here.

    python3 benchmark/tools/strip_trace.py in.xplane.pb out.xplane.pb
"""
import argparse


def varint(buf, i):
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def enc(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def fields(buf):
    """-> [(field number, wire type, value bytes or int, raw bytes)]"""
    i, out = 0, []
    while i < len(buf):
        start = i
        key, i = varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = varint(buf, i)
        elif wt == 2:
            ln, i = varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        out.append((num, wt, val, buf[start:i]))
    return out


def ld(num, payload):
    return enc(num << 3 | 2) + enc(len(payload)) + payload


def strip_plane(plane, keep_host, max_ops):
    fs = fields(plane)
    name = next(v for n, _w, v, _r in fs if n == 2).decode()
    is_dev = name.startswith("/device:") and "CUSTOM" not in name
    if not is_dev and name != "/host:CPU":
        return None
    meta = {}
    for n, _w, v, _r in fs:
        if n == 4:   # map entry: key=1, value=2 (XEventMetadata)
            entry = {k: val for k, _w2, val, _r2 in fields(v)}
            md = {k: val for k, _w2, val, _r2 in fields(entry[2])}
            meta[entry[1]] = md.get(2, b"").decode(errors="replace")
    used, out = set(), bytearray()
    for n, _w, v, raw in fs:
        if n == 3:
            lf = fields(v)
            lname = next((x for k, _w2, x, _r2 in lf if k == 2),
                         b"").decode()
            line, kept = bytearray(), 0
            for k, _w2, x, r2 in lf:
                if k != 4:
                    line += r2
                    continue
                ev = fields(x)
                mid = next(val for kk, _w3, val, _r3 in ev if kk == 1)
                if not is_dev and meta.get(mid) not in keep_host:
                    continue
                if is_dev and lname == "XLA Ops" and kept >= max_ops:
                    continue
                kept += 1
                used.add(mid)
                line += ld(4, b"".join(r3 for kk, _w3, _v3, r3 in ev
                                       if kk != 4))
            if kept:
                out += ld(3, bytes(line))
        elif n in (1, 2):
            out += raw
    for n, _w, v, raw in fs:
        if n == 4:
            entry = {k: val for k, _w2, val, _r2 in fields(v)}
            if entry[1] in used:
                md = b"".join(r2 for k, _w2, _v2, r2 in fields(entry[2])
                              if k in (1, 2))
                out += ld(4, enc(1 << 3) + enc(entry[1]) + ld(2, md))
    return bytes(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--max-ops", type=int, default=400)
    ap.add_argument("--keep", default="bench.window,init.fetch,"
                    "init.write_stall,init.dispatch,romix.dispatch")
    a = ap.parse_args()
    keep = set(a.keep.split(","))
    with open(a.src, "rb") as f:
        space = f.read()
    out = bytearray()
    for n, _w, v, _raw in fields(space):
        if n == 1:
            p = strip_plane(v, keep, a.max_ops)
            if p is not None:
                out += ld(1, p)
    with open(a.dst, "wb") as f:
        f.write(out)
    print(f"{len(space)} -> {len(out)} bytes")


if __name__ == "__main__":
    main()
