"""Share of the WHOLE traced window in which the host had no device
program outstanding: 1 - (union of the ``device.flight`` spans, clipped
to the window) / window, on the host's clock. A flight lasts from the
enqueue until the host has the result, so it is longer than the
program's execution: this is a lower bound of the device's idle share,
over all of the window where the device trace holds only its start."""
from lib import stats

META = {"layer": "device", "unit": "%", "source": "program_span",
        "moves": "proofs_per_s", "better": "lower"}


def window_us(facts):
    """The window's edges on the span clock, from the spans themselves:
    a span cut by an edge says where the edge is (``clipped_us``). A
    span that starts after some span wholly inside was cut by the end;
    one that ends before such a span ends was cut by the start. An edge
    that cuts nothing is placed by the window's length."""
    inside = [s for s in facts.spans if s["inside"]]
    if not inside:
        return None
    first = min(s["ts_us"] for s in inside)
    last = max(s["ts_us"] + s["dur_us"] for s in inside)
    lo = hi = None
    for s in facts.spans:
        if s["inside"]:
            continue
        end = s["ts_us"] + s["dur_us"]
        if s["ts_us"] >= first:
            hi = s["ts_us"] + s["clipped_us"]
        elif end <= last:
            lo = end - s["clipped_us"]
    width = facts.run_window_s * 1e6
    if lo is None:
        lo = first if hi is None else min(hi - width, first)
    if hi is None:
        hi = max(lo + width, last)
    return lo, hi


def starved_share(flights, lo, hi):
    """1 - union of (start, end) pairs clipped to [lo, hi] over hi - lo."""
    out = stats.union_length([(max(a, lo), min(b, hi)) for a, b in flights
                              if min(b, hi) > max(a, lo)])
    return 1.0 - out / (hi - lo)


def read(facts):
    flights = [(s["ts_us"], s["ts_us"] + s["dur_us"])
               for s in facts.spans_named("device.flight", inside=False)]
    win = window_us(facts)
    if not flights or win is None or win[1] <= win[0]:
        return None
    return 100.0 * starved_share(flights, *win)
