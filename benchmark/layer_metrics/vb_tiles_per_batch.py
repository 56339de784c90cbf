"""Label programs one ``post/verifier.verify_many`` call ran as: the
median ``tiles`` attribute of the ``post.verify`` spans (lane tiles
under ``ops/scrypt.lane_ceiling``; 2 for a 256-proof K3=37 batch at
N=8192 on a v5e: 8,192 lanes and the rest). Nothing to read from a
program that does not tile."""
from lib import stats

META = {"layer": "pipeline post/verifier", "unit": "programs",
        "source": "program_span", "moves": "proofs_per_s",
        "better": "lower"}


def read(facts):
    tiles = [s["args"]["tiles"] for s in facts.spans_named("post.verify")
             if "tiles" in s["args"]]
    return stats.median(tiles) if tiles else None
