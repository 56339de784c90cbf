"""What a proof costs outside its passes over the store: median, over
the ``prove.proof`` spans inside the window, of the span's duration
minus the ``prove.window`` spans it contains: the k2pow search, opening
the session and the reader pool, the final fetch and decode of the
winner's indices."""
from lib import stats

META = {"layer": "pipeline post/prover", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    proofs = facts.spans_named("prove.proof")
    if not proofs:
        return None
    passes = [(w["ts_us"], w["ts_us"] + w["dur_us"])
              for w in facts.spans_named("prove.window")]
    fixed = []
    for p in proofs:
        lo, hi = p["ts_us"], p["ts_us"] + p["dur_us"]
        inside = sum(b - a for a, b in passes if lo <= a and b <= hi)
        fixed.append((p["dur_us"] - inside) / 1e3)
    return stats.median(fixed)
