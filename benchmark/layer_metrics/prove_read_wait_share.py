"""Share of the window the prove loop spent blocked on the reader pool
(``prove.read_wait`` spans clipped to the window): near 0 while the
disk, or the page cache, keeps ahead of the scan."""
META = {"layer": "storage post/data", "unit": "%",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    waits = facts.spans_named("prove.read_wait", inside=False)
    window_us = facts.run_window_s * 1e6
    if not waits or window_us <= 0:
        return None
    return 100.0 * sum(s["clipped_us"] for s in waits) / window_us
