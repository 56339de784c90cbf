"""How full the scheduler's packed label programs run: summed real
``lanes`` over summed ``bucket`` (the width the program is dispatched
at) of the ``runtime.pack.dispatch`` spans inside the window. Nothing to
read from a program whose pack spans carry no ``bucket``."""
META = {"layer": "pipeline runtime/scheduler", "unit": "%",
        "source": "program_span", "moves": "labels_per_s",
        "better": "higher"}


def read(facts):
    packs = [s["args"] for s in facts.spans_named("runtime.pack.dispatch")
             if "bucket" in s["args"]]
    sent = sum(a["bucket"] for a in packs)
    return 100.0 * sum(a["lanes"] for a in packs) / sent if sent else None
