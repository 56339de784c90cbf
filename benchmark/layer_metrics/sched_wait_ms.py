"""Median time a verifyd request waited in the tenant scheduler, from
``submit_call`` to the start of its quantum on a worker thread: the
``queue_wait_ms`` of ``runtime.quantum`` spans of kind verifyd. It lies
inside ``verifyd_self_ms``."""
from lib import stats

META = {"layer": "service verifyd", "unit": "ms", "source": "program_span",
        "moves": "p50_ms", "better": "lower"}


def read(facts):
    waits = [s["args"]["queue_wait_ms"]
             for s in facts.spans_named("runtime.quantum")
             if s["args"].get("kind") == "verifyd"
             and "queue_wait_ms" in s["args"]]
    return stats.median(waits) if waits else None
