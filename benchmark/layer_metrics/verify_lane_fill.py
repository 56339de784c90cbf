"""Useful lanes over dispatched lanes in the label recompute: K3 x the
proofs whose POST item reached the device (those not rejected on the
host, by construction of the traffic) over the sum of ``batch`` of the
``romix.dispatch`` spans in the window. Two paddings stand between the
two: proofs to a power of two (verify/farm) and lanes to a power of two
(ops/scrypt.shape_bucket)."""
META = {"layer": "pipeline post/verifier", "unit": "%",
        "source": "program_span", "moves": "p50_ms",
        "better": "higher"}


def read(facts):
    lanes = sum(s["args"].get("batch", 0)
                for s in facts.spans_named("romix.dispatch"))
    proofs = facts.counters.get("device_checked_proofs")
    k3 = facts.counters.get("k3")
    if not lanes or not proofs or not k3:
        return None
    return 100.0 * k3 * proofs / lanes
