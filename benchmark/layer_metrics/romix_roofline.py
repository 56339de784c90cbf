"""The label programs' share of the HBM roofline: the bytes ROMix needs
for the lanes dispatched (2*128*N per lane, padding lanes included)
over the chip's peak HBM bytes/s, over the summed device time of the
label programs. HBM bound assumed: whether the VPU's integer ops bind
instead is open until fill and mix have scopes of their own (PERF.md
section 7). Lanes per execution are the mean ``batch`` of the
``romix.dispatch`` spans in the window, split over the chips used."""
from layer_metrics import label_prog_ms as _lp
from lib import shapes

META = {"layer": "kernels ops/scrypt", "unit": "%",
        "source": "device_trace", "moves": "labels_per_s",
        "better": "higher"}


def read(facts):
    red = facts.reduction
    if red is None or facts.peaks is None:
        return None
    durs = red.program_durations(_lp.PROGRAMS)
    spans = [s for s in facts.spans_named("romix.dispatch")
             if "batch" in s["args"]]
    if not durs or not spans:
        return None
    n = int(spans[0]["args"]["n"])
    lanes_per_exec = (sum(s["args"]["batch"] for s in spans) / len(spans)
                      / max(len(red.chips), 1))
    need = shapes.romix_hbm_bytes(n, 1) * lanes_per_exec * len(durs)
    return 100.0 * (need / facts.peaks["hbm_bytes_per_s"]) / sum(durs)
