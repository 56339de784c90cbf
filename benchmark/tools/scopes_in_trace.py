#!/usr/bin/env python3
"""Do the device ops of a kept trace carry the program's named scopes,
and how much device time does each scope take?

    python3 benchmark/tools/scopes_in_trace.py <kept.xplane.pb | trace dir>

The label, proving-hash and k2pow programs wrap their phases in
``jax.named_scope`` (``pbkdf2_expand``, ``romix_fill``, ``romix_mix``,
``pbkdf2_finish``, ``minscan`` in ``ops/scrypt.py``; ``proving_hash``;
``pow_sha256``): op metadata, no op changes. ``lib/xplane.py`` keys
device ops by HLO name (``%while.80``), which renumbers with every
compile. For the ``XLA Ops`` line of each device plane of a trace kept
with ``run.py --keep-trace`` this prints which field holds the scope
and the device time by scope, looking twice:

``profile_data``  through ``jax.profiler.ProfileData``, which is all the
    harness reads a trace with: an event's name and its own stats.
``xplane_proto``  through the trace's protobuf, where some package here
    brings its Python classes: an ``XLA Ops`` event points at an
    ``XEventMetadata`` (one per HLO op) whose name, display name and
    stats are looked at too. On a v5e the scope is there and only
    there: the metadata's ``tf_op`` stat holds the op's ``op_name``
    path, e.g. ``jit(_labels_fused)/romix_fill/while/body/...``
    (PERF.md section 7).

``sum_s`` adds every event (a while loop and the ops of its body both
count); ``union_s`` is the time in which any op of the scope ran. One
JSON object on stdout."""

import json
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import xplane  # noqa: E402

SCOPES = ("pbkdf2_expand", "romix_fill", "romix_mix", "pbkdf2_finish",
          "minscan", "proving_hash", "pow_sha256")
# as one component of an op_name path: jit(f)/jit(main)/romix_fill/while/...
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?:/|$)")


def _find(fields):
    """-> (scope, field name) of the first (field name, text) pair that
    holds a scope, else (None, None)."""
    for key, text in fields:
        m = _SCOPE.search(text)
        if m:
            return m.group(1), key
    return None, None


def _union_s(starts, ends) -> float:
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    prev = np.concatenate([s[:1], e[:-1]])
    return float(np.clip(e - np.maximum(s, prev), 0, None).sum() / 1e9)


class _Tally:
    """Device time by scope over one ``XLA Ops`` line."""

    def __init__(self, plane: str) -> None:
        self.plane = plane
        self.scope_of: dict = {}    # op key -> scope | None
        self.fields: dict = {}      # field that held a scope -> distinct ops
        self.field_names: dict = {}
        self.example: dict = {}
        self.spans: dict = {}

    def classify(self, key, fields, op_text: str):
        """``fields``: a callable giving [(field name, text)] of one op,
        asked once per distinct op (an op runs thousands of times)."""
        if key not in self.scope_of:
            pairs = fields()
            scope, field = _find(pairs)
            self.scope_of[key] = scope
            for name, _text in pairs:
                self.field_names[name] = self.field_names.get(name, 0) + 1
            if field is not None:
                self.fields[field] = self.fields.get(field, 0) + 1
                self.example.setdefault(scope, {
                    "op": xplane.short_op(op_text), "field": field,
                    "value": dict(pairs)[field][:160]})
        return self.scope_of[key] or "(no scope)"

    def add(self, scope: str, start_ns: float, end_ns: float) -> None:
        st, en = self.spans.setdefault(scope, ([], []))
        st.append(start_ns)
        en.append(end_ns)

    def result(self) -> dict:
        by_scope = {}
        for scope, (st, en) in self.spans.items():
            st, en = np.asarray(st, float), np.asarray(en, float)
            by_scope[scope] = {"events": len(st),
                               "sum_s": float((en - st).sum() / 1e9),
                               "union_s": _union_s(st, en)}
        return {"plane": self.plane,
                "op_events": sum(v["events"] for v in by_scope.values()),
                "distinct_ops": len(self.scope_of),
                "ops_with_a_scope": sum(1 for v in self.scope_of.values()
                                        if v),
                "fields_on_distinct_ops": self.field_names,
                "scope_found_in": self.fields, "by_scope": by_scope,
                "example": self.example}


def scan_profile_data(path: str) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            tally = _Tally(plane.name)
            for ev in line.events:
                scope = tally.classify(
                    ev.name, lambda ev=ev: [("name", ev.name)] + [
                        (f"stat:{k}", str(v)) for k, v in ev.stats],
                    ev.name)
                tally.add(scope, ev.start_ns, ev.start_ns + ev.duration_ns)
            out.append(tally.result())
    return out


def _xplane_pb2():
    for mod in ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
                "tsl.profiler.protobuf.xplane_pb2",
                "xprof.protobuf.xplane_pb2"):
        try:
            return __import__(mod, fromlist=["XSpace"])
        except ImportError:
            continue
    return None


def scan_xplane_proto(path: str) -> list | None:
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}

        def fields(md):
            pairs = [("XEventMetadata.name", md.name),
                     ("XEventMetadata.display_name", md.display_name)]
            for st in md.stats:
                text = st.str_value or stat_name.get(st.ref_value, "")
                if text:
                    pairs.append(("XEventMetadata.stats["
                                  f"{stat_name.get(st.metadata_id)}]", text))
            return pairs

        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            tally = _Tally(plane.name)
            t0 = line.timestamp_ns
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                scope = tally.classify(ev.metadata_id,
                                       lambda md=md: fields(md), md.name)
                start = t0 + ev.offset_ps / 1e3
                tally.add(scope, start, start + ev.duration_ps / 1e3)
            out.append(tally.result())
    return out


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        kept = os.path.join(target, "kept.xplane.pb")
        target = kept if os.path.exists(kept) else xplane.find_xplane(target)
    print(json.dumps({"trace": target,
                      "profile_data": scan_profile_data(target),
                      "xplane_proto": scan_xplane_proto(target)}))
