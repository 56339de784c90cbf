"""How long a request goes on after its POST verdicts are in: per
request (``req``), the end of its last ``farm.request`` of another kind
(signature, membership, k2pow, VRF) minus the end of its last one of
kind post, floored at 0; the median over requests that have both. Over
0 means the host kinds, not POST, close the request (ROADMAP S7)."""
from lib import stats

META = {"layer": "pipeline verify/farm", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    ends: dict = {}     # req -> [last post end, last other end]
    for s in facts.spans_named("farm.request"):
        req = s["args"].get("req")
        if req is None:
            continue
        pair = ends.setdefault(req, [None, None])
        k = 0 if s["args"].get("kind") == "post" else 1
        end = s["ts_us"] + s["dur_us"]
        if pair[k] is None or end > pair[k]:
            pair[k] = end
    tails = [max(other - post, 0) / 1e3
             for post, other in ends.values()
             if post is not None and other is not None]
    return stats.median(tails) if tails else None
