"""(max - min per-chip busy time) / max, from the trace; only where
more than one chip ran."""
META = {"layer": "sharding parallel/mesh", "unit": "%",
        "source": "device_trace", "moves": "labels_per_s",
        "better": "lower"}


def read(facts):
    red = facts.reduction
    if red is None or len(red.chips) < 2:
        return None
    busy = [c["busy_s"] for c in red.chips]
    return 100.0 * (max(busy) - min(busy)) / max(busy) if max(busy) else None
