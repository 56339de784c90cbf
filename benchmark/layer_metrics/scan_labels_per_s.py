"""Store labels scanned per second: the ``count`` of the
``prove.retire`` spans inside the window (a batch is retired when its
count vectors are on the host) over the window's seconds. Every label is
scanned under all the nonces of a pass. The gaps between proofs (k2pow,
session, decode) are inside it."""
META = {"layer": "pipeline post/prover", "unit": "labels/s",
        "source": "program_span", "moves": "p50_ms", "better": "higher"}


def read(facts):
    counts = [s["args"]["count"] for s in facts.spans_named("prove.retire")
              if "count" in s["args"]]
    if not counts or facts.run_window_s <= 0:
        return None
    return sum(counts) / facts.run_window_s
