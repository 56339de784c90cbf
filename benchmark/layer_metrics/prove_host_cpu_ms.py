"""CPU time the prove loop's thread spends putting one flight on the
device: mean, over the ``prove.dispatch`` spans inside the window, of
the summed ``cpu_us`` (the thread's CPU time, utils/tracing.py) of the
``prove.convert``, ``prove.upload`` and ``prove.enqueue`` spans it
holds. The CPU side of what ``prove_dispatch_ms`` times on the wall
clock: near it, the stretch is computation; far under it, waiting (the
interpreter lock, the transfer). Nothing to read where the spans carry
no ``cpu_us``."""
META = {"layer": "pipeline post/prover", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}

STAGES = ("prove.convert", "prove.upload", "prove.enqueue")


def read(facts):
    dispatches = facts.spans_named("prove.dispatch")
    cpu: dict = {}
    for name in STAGES:
        for s in facts.spans_named(name, inside=False):
            if "cpu_us" in s["args"]:
                parent = s["args"].get("parent")
                cpu[parent] = cpu.get(parent, 0) + s["args"]["cpu_us"]
    if not dispatches or not cpu:
        return None
    own = [cpu.get(d["args"].get("id"), 0) for d in dispatches]
    return sum(own) / len(own) / 1e3
