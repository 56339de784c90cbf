"""Median time a POST request waited in the farm's lane queue before
its batch was taken: the ``queue_wait_ms`` the farm records on each
``farm.request`` span of kind post. (The ``farm.lane_wait`` span the
issue names exists only under lane backpressure, 8,192 queued, which
this traffic never reaches.)"""
from lib import stats

META = {"layer": "pipeline verify/farm", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    waits = [s["args"]["queue_wait_ms"]
             for s in facts.spans_named("farm.request")
             if s["args"].get("kind") == "post"
             and "queue_wait_ms" in s["args"]]
    return stats.median(waits) if waits else None
