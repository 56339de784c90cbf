"""Host time of one batch's host-to-device copies: mean duration of the
``prove.upload`` spans inside the window (three arrays a batch today)."""
META = {"layer": "pipeline post/prover", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    d = [s["dur_us"] for s in facts.spans_named("prove.upload")]
    return sum(d) / len(d) / 1e3 if d else None
