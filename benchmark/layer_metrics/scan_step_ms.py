"""Median device duration of one scan-step program (the XLA module of
the step the ``Prover`` bound: ``jit_prove_scan_step_pallas`` on one
device, ``jit_prove_scan_step_jit`` for the XLA step) wholly inside the
window: one nonce group over one label batch: kernel, mask unpacking,
compaction and merge."""
from lib import stats

META = {"layer": "kernels ops/proving_pallas", "unit": "ms",
        "source": "device_trace", "moves": "p50_ms", "better": "lower"}
PROGRAMS = r"prove_scan_step"


def read(facts):
    red = facts.reduction
    if red is None:
        return None
    durs = red.program_durations(PROGRAMS)
    return 1e3 * stats.median(durs) if durs else None
