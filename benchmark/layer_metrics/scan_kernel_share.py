"""The Pallas kernel's share of the scan-step program's device time:
summed time of the kernel's op events over the summed time of the step
programs in the window. The rest of the program is the epilogue: mask
unpacking, hit compaction (``scan_compact``) and merge (``scan_merge``).
(The programs' sum leaves out the trace's first and last execution,
``lib/xplane.py``: two of thousands.)"""
from layer_metrics import scan_roofline as _kernel
from layer_metrics import scan_step_ms as _step

META = {"layer": "kernels ops/proving_pallas", "unit": "%",
        "source": "device_trace", "moves": "p50_ms", "better": "higher"}


def read(facts):
    red = facts.reduction
    if red is None:
        return None
    took = _kernel.kernel_seconds(red)
    durs = red.program_durations(_step.PROGRAMS)
    if took <= 0 or not durs:
        return None
    return 100.0 * took / sum(durs)
