"""The Pallas scan KERNEL's share of its roofline, over the kernel's
own op events (the ``_scan_pallas`` custom-call on the ``XLA Ops``
line), not over the step program around it.

Least time of the calls made in the window: the larger of 16 B x labels
over ``hbm_bytes_per_s`` and 640 u32 ops x labels x nonces over
``int8_op_per_s`` (``lib/shapes_prove.py``; labels as dispatched,
padding included; both peaks from ``lib/device.PEAKS`` as it stands),
over the kernel's summed device time. At 16 nonces a call THE OP BOUND
IS THE LARGER. No u32 peak of the v5e's vector unit is published and
the table's integer peak is the MXU's int8 figure, so the share
understates how close the kernel is to the VPU's limit; no peak is
invented, and the share cannot read over 100%.

Calls: ``groups`` kernel calls per ``prove.enqueue`` annotation that
starts inside the trace's window (the same clock as the op events);
labels and nonces per call from that span's attributes."""
from lib import shapes_prove

META = {"layer": "kernels ops/proving_pallas", "unit": "%",
        "source": "device_trace", "moves": "p50_ms", "better": "higher"}
KERNEL = "_scan_pallas"


def kernel_seconds(red) -> float:
    """Summed device time of the kernel's op events in the window."""
    return sum(secs for c in red.chips for name, secs in c["ops"].items()
               if KERNEL in name and "custom-call" in name)


def read(facts):
    red = facts.reduction
    shape = [s["args"] for s in facts.spans_named("prove.enqueue")
             if "batch" in s["args"]]
    if red is None or facts.peaks is None or not shape:
        return None
    took = kernel_seconds(red)
    lo, hi = red.window_ns
    calls = shape[0]["groups"] * sum(
        1 for name, s, _e in red.host_spans
        if name == "prove.enqueue" and lo <= s < hi)
    if took <= 0 or not calls:
        return None
    least = shapes_prove.scan_least_s(
        shape[0]["batch"], shape[0]["nonces"] // shape[0]["groups"],
        facts.peaks)
    return 100.0 * least["seconds"] * calls / took
