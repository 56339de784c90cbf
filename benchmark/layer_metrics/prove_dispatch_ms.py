"""Host time to put one label batch on the device: mean duration of the
``prove.dispatch`` spans inside the window, each minus the
``prove.read_wait`` nested in it: convert + upload + enqueue."""
META = {"layer": "pipeline post/prover", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    spans = facts.spans_named("prove.dispatch")
    if not spans:
        return None
    waited: dict = {}
    for w in facts.spans_named("prove.read_wait", inside=False):
        parent = w["args"].get("parent")
        waited[parent] = waited.get(parent, 0) + w["dur_us"]
    own = [s["dur_us"] - waited.get(s["args"].get("id"), 0) for s in spans]
    return sum(own) / len(own) / 1e3
