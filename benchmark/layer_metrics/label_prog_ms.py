"""Median device duration of one label program (the XLA modules
``jit__labels_min_fused`` / ``jit__labels_fused``: PBKDF2 expand, ROMix,
finish and, in init, the VRF min-scan) in the trace."""
from lib import stats

META = {"layer": "kernels ops/scrypt", "unit": "ms",
        "source": "device_trace", "moves": "labels_per_s",
        "better": "lower"}
PROGRAMS = r"labels_(min_)?fused"


def read(facts):
    red = facts.reduction
    if red is None:
        return None
    durs = red.program_durations(PROGRAMS)
    return 1e3 * stats.median(durs) if durs else None
