"""Median ``post.verify.relayout`` duration: on one device the labels
come to the host as bytes, are turned into words, padded and uploaded
again for the proving hash; the sharded path keeps them on the device
(ROADMAP D5). None where no call took the single-device branch."""
from lib import stats

META = {"layer": "pipeline post/verifier", "unit": "ms",
        "source": "program_span", "moves": "p50_ms", "better": "lower"}


def read(facts):
    durs = [s["dur_us"] / 1e3
            for s in facts.spans_named("post.verify.relayout")]
    return stats.median(durs) if durs else None
