"""Median self time of a ``verifyd.request`` span: its duration minus
what its ``farm.request`` descendants cover (admission, the tenant
scheduler's queue and thread hop, JSON in and out are what is left)."""
from lib import stats

META = {"layer": "service verifyd", "unit": "ms",
        "source": "program_span", "moves": "p50_ms",
        "better": "lower"}


def read(facts):
    reqs = {s["args"]["id"]: s for s in facts.spans_named("verifyd.request")}
    if not reqs:
        return None
    drain_parent = {s["args"]["id"]: s["args"].get("parent")
                    for s in facts.spans_named("verifyd.drain")}
    cover: dict = {}
    for s in facts.spans_named("farm.request"):
        top = drain_parent.get(s["args"].get("parent"))
        if top in reqs:
            cover.setdefault(top, []).append(
                (s["ts_us"], s["ts_us"] + s["dur_us"]))
    selfs = []
    for rid, r in reqs.items():
        lo, hi = r["ts_us"], r["ts_us"] + r["dur_us"]
        covered = stats.union_length(
            [(max(a, lo), min(b, hi)) for a, b in cover.get(rid, [])
             if min(b, hi) > max(a, lo)])
        selfs.append((r["dur_us"] - covered) / 1e3)
    return stats.median(selfs)
