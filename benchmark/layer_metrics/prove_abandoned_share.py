"""Share of the flights the prover put on the device that an early exit
threw away: summed ``abandoned`` over summed ``flights`` of the
``prove.window`` spans inside the window (runtime/engine.Pipeline drops
the tickets still in flight when the winner is decided; the device runs
them all the same and the pass's decode waits for them). Nothing to read
on windows without those attributes."""
META = {"layer": "pipeline post/prover", "unit": "%",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    windows = [w for w in facts.spans_named("prove.window")
               if "flights" in w["args"]]
    flights = sum(w["args"]["flights"] for w in windows)
    if not flights:
        return None
    return 100.0 * sum(w["args"]["abandoned"] for w in windows) / flights
