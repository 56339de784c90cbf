"""The traced run's per-layer metrics: every reader found in
``benchmark/layer_metrics/`` that ``BENCHMARK.json`` lists for the cell
is asked; one that returns None is left out. A reader is a module with

    META = {"layer": ..., "unit": ..., "source": ..., "moves": ...,
            "better": ...}
    def read(facts) -> float | None

It says nothing about cells: the ``workloads`` list of its entry under
``per_layer`` does (no list: every cell), so a new cell gets an
existing metric by being named there. A reader returns None where it
finds nothing to read (no trace, no such span, a closed loop, one chip).

``facts`` (:class:`Facts`) carries what a reader may look at: the
reduced device trace, the program's host spans and counters inside the
window, the generator's own record, the configuration and the peaks.
A metric is only reported where the end-to-end metric it moves is.
"""

from __future__ import annotations

import dataclasses
import importlib
import sys

from . import device, xplane


@dataclasses.dataclass
class Facts:
    run: object                 # run.Run
    reduction: object | None    # xplane.Reduction (None: no trace found)
    spans: list                 # program host spans overlapping the window:
    #                             dicts {name, ts_us, dur_us, inside,
    #                             clipped_us, args} (lib/tracewin.py)
    counters: dict              # program counters/stage clocks, window
    generator: dict             # the load generator's own record
    peaks: dict | None
    end_to_end: dict
    run_window_s: float = 0.0   # the window on the host clock

    def spans_named(self, name: str, inside: bool = True) -> list:
        """Spans of one name: those wholly inside the window, or with
        ``inside=False`` every one that overlaps it (use their
        ``clipped_us``)."""
        return [s for s in self.spans if s["name"] == name
                and (s["inside"] or not inside)]


def read_all(run, res):
    """-> (metrics dict, breakdown dict | None, {"busy_s", "window_s"})"""
    red = None
    if res.get("trace_data") is not None:
        red = xplane.reduce_data(
            res["trace_data"], span_names=res.get("gap_spans", ()),
            window_span="bench.window",
            idle_label=res.get("idle_label", "no-span"))
        if red.dropped:
            print("benchmark: the device dropped trace buffers: device "
                  "metrics of this run are short of the truth",
                  file=sys.stderr)
    facts = Facts(run=run, reduction=red, spans=res.get("spans", []),
                  counters=res.get("counters", {}),
                  generator=res.get("generator", {}),
                  peaks=(None if run.rehearse
                         else device.peaks(run.device["kind"])),
                  end_to_end=res["end_to_end"],
                  run_window_s=res.get("window_s", 0.0))
    metrics = {}
    end_to_end = run.declared("end_to_end")
    for name in sorted(run.declared("per_layer")):
        mod = importlib.import_module(f"layer_metrics.{name}")
        if mod.META["moves"] not in end_to_end:
            continue
        try:
            value = mod.read(facts)
        except Exception as e:  # noqa: BLE001 - one reader must not lose the line
            print(f"benchmark: layer metric {name} failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            value = None
        if value is not None:
            metrics[name] = {"value": float(value),
                             "unit": mod.META["unit"]}
    breakdown = None
    busy = {"busy_s": 0.0, "window_s": res.get("window_s", 0.0)}
    if red is not None and red.chips:
        breakdown = {"device_ops": (red.top_programs(4)
                                    + red.top_ops(6))[:10],
                     "idle_gaps": red.top_gaps(10)}
        busy = {"busy_s": red.busy_s, "window_s": red.window_s}
    return metrics, breakdown, busy
