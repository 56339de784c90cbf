"""Useful lanes over dispatched lanes in the label recompute, counted
where the lanes are packed: the sum of ``lanes_valid`` over the sum of
``lanes`` of the ``post.verify`` spans. ``verify_lane_fill`` takes the
same numerator from the generator, by construction of the traffic; the
two should agree within a point."""
META = {"layer": "pipeline post/verifier", "unit": "%",
        "source": "program_span", "moves": "p50_ms", "better": "higher"}


def read(facts):
    calls = [s for s in facts.spans_named("post.verify")
             if "lanes" in s["args"]]
    lanes = sum(s["args"]["lanes"] for s in calls)
    if not lanes:
        return None
    return 100.0 * sum(s["args"]["lanes_valid"] for s in calls) / lanes
