"""Host time a proof keeps the scan off the device outside the steady
stream of its passes: for each ``prove.proof`` wholly inside the window,
the time from the start of its ``prove.open`` (the last one on the same
thread that ended before the proof began: a client builds a ``Prover``
per challenge) to the proof's end during which no ``device.flight`` and
no ``prove.k2pow`` of that thread is open; the median. Opening the
store, the session's and each pass's set-up, a pass's fill (before its
first flight is out) and drain, the decode's wait for abandoned flights,
the close. Nothing to read on spans without ``prove.open``."""
from lib import stats

META = {"layer": "pipeline post/prover", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    proofs = facts.spans_named("prove.proof")
    opens = facts.spans_named("prove.open", inside=False)
    if not proofs or not opens:
        return None
    busy = [s for s in facts.spans
            if s["name"] in ("device.flight", "prove.k2pow")]
    edges = []
    for p in proofs:
        begin, end = p["ts_us"], p["ts_us"] + p["dur_us"]
        before = [o for o in opens if o["tid"] == p["tid"]
                  and o["ts_us"] + o["dur_us"] <= begin]
        if not before:
            continue
        lo = max(before, key=lambda o: o["ts_us"] + o["dur_us"])["ts_us"]
        covered = stats.union_length(
            (max(s["ts_us"], lo), min(s["ts_us"] + s["dur_us"], end))
            for s in busy if s["tid"] == p["tid"]
            and s["ts_us"] < end and s["ts_us"] + s["dur_us"] > lo)
        edges.append((end - lo - covered) / 1e3)
    return stats.median(edges) if edges else None
