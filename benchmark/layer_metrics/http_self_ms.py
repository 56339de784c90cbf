"""Median self time of a ``verifyd.http`` span: its duration minus its
``verifyd.request`` child (JSON in, request objects built, admission
up to the request span, verdicts to a response body)."""
from lib import stats

META = {"layer": "service verifyd", "unit": "ms", "source": "program_span",
        "moves": "p50_ms", "better": "lower"}


def read(facts):
    https = facts.spans_named("verifyd.http")
    if not https:
        return None
    child = {s["args"].get("parent"): s["dur_us"]
             for s in facts.spans_named("verifyd.request")}
    return stats.median([(s["dur_us"] - child.get(s["args"]["id"], 0)) / 1e3
                         for s in https])
