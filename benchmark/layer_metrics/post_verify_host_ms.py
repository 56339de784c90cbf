"""Median host time of one ``post/verifier.verify_many`` call: the
``post.verify`` span's duration minus the union of its ``device.flight``
children (the stretches in which a device program was outstanding).
Structural checks, host k2pow, the K3 subset, packing, the eager bucket
pad, label bytes to words and back up, the threshold."""
from lib import stats

META = {"layer": "pipeline post/verifier", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    calls = facts.spans_named("post.verify")
    if not calls:
        return None
    flights: dict = {}
    for f in facts.spans_named("device.flight", inside=False):
        flights.setdefault(f["args"].get("parent"), []).append(
            (f["ts_us"], f["ts_us"] + f["dur_us"]))
    host = []
    for c in calls:
        lo, hi = c["ts_us"], c["ts_us"] + c["dur_us"]
        out = stats.union_length(
            [(max(a, lo), min(b, hi))
             for a, b in flights.get(c["args"]["id"], [])
             if min(b, hi) > max(a, lo)])
        host.append((c["dur_us"] - out) / 1e3)
    return stats.median(host)
