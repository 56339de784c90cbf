"""Small arithmetic shared by the harness and the readers."""

from __future__ import annotations


def percentile(values, q: float):
    """Linear-interpolated percentile (q in [0, 100]) of a non-empty
    list; None for an empty one."""
    v = sorted(values)
    if not v:
        return None
    if len(v) == 1:
        return float(v[0])
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def median(values):
    return percentile(values, 50.0)


def union_length(intervals) -> float:
    """Total length of the union of (start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
