"""What a request spends outside the server: the generator's median
latency minus the median duration of the server's ``verifyd.http`` span
(body read to response object). Left over: the client, loopback, the
child process, aiohttp's own parsing of the request line and headers,
and in an open loop how late the generator sent."""
from lib import stats

META = {"layer": "load generator", "unit": "ms", "source": "program_span",
        "moves": "p50_ms", "better": "lower"}


def read(facts):
    lat = facts.generator.get("latency_ms")
    http = [s["dur_us"] / 1e3 for s in facts.spans_named("verifyd.http")]
    if not lat or not http:
        return None
    return stats.median(lat) - stats.median(http)
