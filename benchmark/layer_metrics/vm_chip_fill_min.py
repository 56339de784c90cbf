"""The least filled chip of a sharded verify batch: per ``post.verify``
span inside the window, the smallest ``chip_lanes_valid / chip_lanes``
over the chips it dispatched lanes to (real lanes over dispatched ones,
summed over the call's tiles); the median over the spans. A tile's
padding sits at its end, so it lands on the last chips: 0 where a chip
held padding only (a 256-proof batch at K3 = 37 in one 16,384-lane
program on four chips). Nothing to read from a program whose spans do
not count lanes per chip."""
from lib import stats

META = {"layer": "pipeline post/verifier", "unit": "%",
        "source": "program_span", "moves": "proofs_per_s",
        "better": "higher"}


def read(facts):
    fills = []
    for s in facts.spans_named("post.verify"):
        real, sent = (s["args"].get("chip_lanes_valid"),
                      s["args"].get("chip_lanes"))
        if real and sent:
            fills.append(min(r / d for r, d in zip(real, sent) if d))
    return 100.0 * stats.median(fills) if fills else None
