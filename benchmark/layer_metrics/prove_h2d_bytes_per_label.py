"""Bytes copied to the device per label dispatched: the ``h2d_bytes``
of the ``prove.upload`` spans inside the window over the ``batch`` of
the ``prove.enqueue`` spans (labels as dispatched, padding included).
24 while the host sends label words and both index halves; 16 once the
device makes its own indices (ROADMAP S6)."""
META = {"layer": "pipeline post/prover", "unit": "B",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    sent = [s["args"]["h2d_bytes"] for s in facts.spans_named("prove.upload")
            if "h2d_bytes" in s["args"]]
    labels = [s["args"]["batch"] for s in facts.spans_named("prove.enqueue")
              if "batch" in s["args"]]
    if not sent or not labels:
        return None
    # per batch, so a window edge between a batch's upload and its
    # enqueue does not skew the ratio
    return (sum(sent) / len(sent)) / (sum(labels) / len(labels))
