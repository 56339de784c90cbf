"""The host's work of retiring one pack, past the wait for the device:
per ``runtime.pack.retire`` span inside the window, the summed duration
of its ``runtime.segment`` (split, write, VRF fold per tenant) and
``runtime.checkpoint`` (fsync and metadata save) children; the median
over packs."""
from lib import stats

META = {"layer": "pipeline runtime/scheduler", "unit": "ms",
        "source": "program_span", "moves": "labels_per_s",
        "better": "lower"}
PARTS = ("runtime.segment", "runtime.checkpoint")


def read(facts):
    per_pack = {s["args"]["id"]: 0.0
                for s in facts.spans_named("runtime.pack.retire")
                if "id" in s["args"]}
    for s in facts.spans:
        parent = s["args"].get("parent")
        if s["name"] in PARTS and parent in per_pack:
            per_pack[parent] += s["dur_us"] / 1e3
    return stats.median(list(per_pack.values())) if per_pack else None
