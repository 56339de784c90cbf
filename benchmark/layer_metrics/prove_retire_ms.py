"""Host time blocked fetching one batch's count vectors: mean duration
of the ``prove.retire`` spans inside the window (the wait for the
device is inside it: long where the device sets the pace, short where
the host does)."""
META = {"layer": "pipeline post/prover", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    d = [s["dur_us"] for s in facts.spans_named("prove.retire")]
    return sum(d) / len(d) / 1e3 if d else None
