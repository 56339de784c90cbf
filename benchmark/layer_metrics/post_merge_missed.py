"""Share of POST batches the farm took while another POST batch was
still in flight (``inflight`` >= 1 on the ``farm.batch`` span): they
queued behind it on the device and could have been one wider program at
no cost in device time."""
META = {"layer": "pipeline verify/farm", "unit": "%",
        "source": "program_span", "moves": "proofs_per_s",
        "better": "lower"}


def read(facts):
    batches = [s for s in facts.spans_named("farm.batch")
               if s["args"].get("kind") == "post"
               and "inflight" in s["args"]]
    if not batches:
        return None
    return 100.0 * sum(1 for s in batches
                       if s["args"]["inflight"] >= 1) / len(batches)
