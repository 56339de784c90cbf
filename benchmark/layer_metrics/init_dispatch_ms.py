"""Host time to enqueue one init batch: mean duration of the
``init.dispatch`` spans inside the window (the interval
PipelineStats.dispatch_s sums)."""
META = {"layer": "pipeline post/initializer", "unit": "ms",
        "source": "program_span", "moves": "labels_per_s",
        "better": "lower"}


def read(facts):
    d = [s["dur_us"] for s in facts.spans_named("init.dispatch")]
    return sum(d) / len(d) / 1e3 if d else None
