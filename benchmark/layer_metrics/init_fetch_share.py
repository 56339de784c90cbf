"""Share of the window the init loop spent blocked fetching label
batches from the device (``init.fetch`` spans minus the writer
backpressure nested in them): how device-bound the host loop is."""
META = {"layer": "pipeline post/initializer", "unit": "%",
        "source": "program_span", "moves": "labels_per_s",
        "better": "higher"}


def read(facts):
    fetch = sum(s["clipped_us"]
                for s in facts.spans_named("init.fetch", inside=False))
    stall = sum(s["clipped_us"]
                for s in facts.spans_named("init.write_stall", inside=False))
    window_us = facts.run_window_s * 1e6
    if not fetch or window_us <= 0:
        return None
    return 100.0 * (fetch - stall) / window_us
