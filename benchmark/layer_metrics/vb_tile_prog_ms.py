"""Median device duration of one label program AT THE CEILING WIDTH
(``jit__labels_fused``, a full lane tile: 8,192 lanes at N=8192 on a
v5e) in the trace.

The trace names a program, not its width, and a batch runs a full tile
and a narrower remainder. :func:`by_width` tells them apart: a flight's
host time over its lanes gives seconds a lane, and an execution goes to
the dispatched width whose expected time is nearest on a log scale
(the widths a batch mixes are 4-8x apart and a label program's time
grows with its lanes, so the nearest is not in doubt). The width is
INFERRED, from a host-clock time a lane, not read from the trace: if a
cell ever mixes widths closer than 4x apart, the program has to carry
its width in its name instead (``docs/OBSERVABILITY.md`` scopes)."""
import math

from layer_metrics import label_prog_ms as _lp
from lib import stats

META = {"layer": "kernels ops/scrypt", "unit": "ms",
        "source": "device_trace", "moves": "proofs_per_s",
        "better": "lower"}


def tile_dispatches(facts) -> list:
    """The window's ``romix.dispatch`` spans that say how wide they
    were: in this cell every label program is a lane tile of a verify
    flight (nothing else computes labels in the process)."""
    return [s for s in facts.spans_named("romix.dispatch", inside=False)
            if "batch" in s["args"]]


def by_width(facts) -> dict:
    """{lanes: [device seconds of each label-program execution the
    reduction kept]}; empty without a trace, tiles or flights."""
    red = facts.reduction
    if red is None:
        return {}
    durs = red.program_durations(_lp.PROGRAMS)
    widths = sorted({s["args"]["batch"] for s in tile_dispatches(facts)})
    per_lane = [f["dur_us"] / 1e6 / f["args"]["lanes"]
                for f in facts.spans_named("device.flight")
                if f["args"].get("program") == "labels_proving"
                and f["args"].get("lanes")]
    if not durs or not widths or not per_lane:
        return {}
    rate = stats.median(per_lane)
    out: dict = {}
    for d in durs:
        w = min(widths, key=lambda w: abs(math.log(d / (rate * w))))
        out.setdefault(w, []).append(d)
    return out


def read(facts):
    split = by_width(facts)
    if not split:
        return None
    durs = split.get(max(s["args"]["batch"]
                         for s in tile_dispatches(facts)))
    return 1e3 * stats.median(durs) if durs else None
