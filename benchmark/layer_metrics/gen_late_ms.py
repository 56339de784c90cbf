"""99th percentile of how late the load generator sent a request (sent
minus due): a starved generator is not a fast server. Open loop only;
in a closed loop a request is due when it is sent."""
from lib import stats

META = {"layer": "load generator", "unit": "ms", "source": "host_clock",
        "moves": "p50_ms", "better": "lower"}


def read(facts):
    late = facts.generator.get("late_ms")
    if not late or not any(late):
        return None
    return stats.percentile(late, 99.0)
