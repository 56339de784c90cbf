"""Share of the traced window in which no operation ran on the device;
on several chips the worst chip's."""
META = {"layer": "device", "unit": "%", "source": "device_trace",
        "moves": "labels_per_s", "better": "lower"}


def read(facts):
    red = facts.reduction
    if red is None or red.worst_idle_share is None:
        return None
    return 100.0 * red.worst_idle_share
