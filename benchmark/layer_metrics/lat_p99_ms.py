"""99th percentile of request latency in a window too short to hold the
1,000 requests an end-to-end p99 needs: reported beside p50_ms, held to
no bound."""
from lib import stats

META = {"layer": "service verifyd", "unit": "ms", "source": "host_clock",
        "moves": "p50_ms", "better": "lower"}


def read(facts):
    lat = facts.generator.get("latency_ms")
    return stats.percentile(lat, 99.0) if lat and len(lat) >= 50 else None
