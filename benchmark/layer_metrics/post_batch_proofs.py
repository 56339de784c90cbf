"""Median number of proofs in one POST batch of the farm (``n`` of the
``farm.batch`` spans of kind post)."""
from lib import stats

META = {"layer": "pipeline verify/farm", "unit": "proofs",
        "source": "program_span", "moves": "p50_ms", "better": "higher"}


def read(facts):
    n = [s["args"]["n"] for s in facts.spans_named("farm.batch")
         if s["args"].get("kind") == "post"]
    return stats.median(n) if n else None
