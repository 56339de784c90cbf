"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: per-chip busy seconds, time by program name,
and the idle gaps attributed to what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. The
reduction is checked on the small recorded trace in
``benchmark/testdata/`` by ``benchmark/selftest.py``.

Vocabulary of a TPU trace as this runtime writes it (looked at by hand
on the chip, PR 22): one plane per chip named ``/device:TPU:<i>``; on
it a line ``XLA Modules`` with one event per program execution, named
``jit_<function>(<fingerprint>)``, and a line ``XLA Ops`` with one
event per executed HLO op (630,000 a second under the label program:
the device's trace buffer holds about 6.3 million and then drops
everything, modules included, and says so with one ``Trace Buffers
Dropped`` event on the line ``XLA TraceMe``; keep a traced window under
about 8 s of label programs). Host threads live on ``/host:CPU``, one
line per thread; ``jax.profiler.TraceAnnotation`` spans (the program's
own spans, bridged by ``SPACEMESH_TRACE_JAX``) are events there, on
the same clock as the device events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log dir."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def program_name(event_name: str) -> str:
    """``jit__labels_min_fused(1234567)`` -> ``jit__labels_min_fused``."""
    return _FINGERPRINT.sub("", event_name).strip()


def union_seconds(intervals, lo: float, hi: float):
    """-> (busy seconds, [gap (start, end)]) of the union of
    ``intervals`` (ns pairs) clipped to the window [lo, hi)."""
    busy = 0.0
    gaps = []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
            cur = s
        if e > cur:
            busy += e - cur
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy / 1e9, gaps


_OPCODE = re.compile(r"[\}\]\)] ([a-z][\w-]*)\(")


def short_op(name: str) -> str:
    """``%fusion.191 = u32[...] fusion(...), kind=kCustom, ...`` ->
    ``%fusion.191 fusion kCustom``: an HLO op's full text is hundreds of
    characters."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:96]
    m = _OPCODE.search(rhs)
    kind = re.search(r"kind=(\w+)", rhs)
    return " ".join(x for x in (lhs, m.group(1) if m else "",
                                kind.group(1) if kind else "") if x)[:96]


@dataclasses.dataclass
class Reduction:
    window_s: float
    chips: list            # [{"chip": i, "busy_s": .., "programs": {name: [durations s]}}]
    host_spans: list       # [(name, start_ns, end_ns)] annotations seen
    gaps_by_span: dict     # span name -> idle seconds on the chosen chip
    window_ns: tuple       # (lo, hi)
    dropped: bool = False  # the device said it dropped trace buffers

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return (sum(c["busy_s"] for c in self.chips) / len(self.chips)
                if self.chips else 0.0)

    @property
    def worst_idle_share(self) -> float | None:
        if not self.chips or self.window_s <= 0:
            return None
        return 1.0 - min(c["busy_s"] for c in self.chips) / self.window_s

    def program_durations(self, pattern: str) -> list:
        """Device durations (s) of every execution, on any chip, of the
        programs whose name matches the regex ``pattern``."""
        rx = re.compile(pattern)
        out = []
        for c in self.chips:
            for name, durs in c["programs"].items():
                if rx.search(name):
                    out.extend(durs)
        return out

    def top_programs(self, n: int = 10) -> list:
        total: dict = {}
        for c in self.chips:
            for name, durs in c["programs"].items():
                total[name] = total.get(name, 0.0) + sum(durs)
        scale = 1.0 / max(len(self.chips), 1)   # seconds per chip
        return sorted(([k, v * scale] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_ops(self, n: int = 10) -> list:
        """Device ops by summed time (seconds per chip); nested ops (a
        while loop and its body) both count, so the list does not add
        up to busy time. Falls back to programs where the trace has no
        op line."""
        total: dict = {}
        for c in self.chips:
            for name, secs in c["ops"].items():
                total[name] = total.get(name, 0.0) + secs
        if not total:
            return self.top_programs(n)
        scale = 1.0 / max(len(self.chips), 1)
        return sorted(([k, v * scale] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.gaps_by_span.items()),
                      key=lambda kv: -kv[1])[:n]


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns
                                                 + ev.duration_ns)


def reduce(path: str, span_names=(), window_ns=None,
           idle_label: str = "no-span",
           window_span: str | None = None) -> Reduction:
    """Reduce one ``.xplane.pb``.

    ``span_names``: host annotation names that may own an idle gap (the
    program's spans, innermost wins: the one that started last among
    those open at the gap's midpoint). ``window_ns``: (lo, hi) on the
    trace clock; ``window_span`` names a host annotation whose interval
    is the window (the harness wraps the traced window in one); default
    is from the first to the last device event.
    Gap attribution is done on the chip with the most idle time."""
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_file(path), span_names=span_names,
                       window_ns=window_ns, idle_label=idle_label,
                       window_span=window_span)


def reduce_data(data, span_names=(), window_ns=None,
                idle_label: str = "no-span",
                window_span: str | None = None) -> Reduction:
    """:func:`reduce` over a ``ProfileData`` already in memory.

    Busy means a program is executing: the union of the ``XLA Modules``
    events. (The ops inside a program leave sub-microsecond holes
    between them that say nothing about whether the host kept the chip
    fed.) Program durations count only executions that lie wholly
    inside the window, and never the first or the last execution of a
    program that a chip's trace holds: a program running when the trace
    began, or when it ended, is recorded as a shorter execution that
    begins or ends with the trace (seen on the chip: "executions" of
    0.50 s and 1.42 s of a 2.83 s program, the second ending a hair
    inside the window).

    When the device dropped trace buffers, what it holds ends early:
    the window's end is then pulled in to the end of the last execution
    recorded (on the chip that stopped first), and busy, idle and the
    gaps are taken over that shorter window, which is what
    ``Reduction.window_s`` then says."""
    chips = []
    dropped = False
    span_set = set(span_names)
    host_spans = []
    window_found = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            modules, ops = [], []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules = list(_events(line))
                elif line.name == OPS_LINE:
                    ops = line
                elif line.name == "XLA TraceMe":
                    dropped = dropped or any(
                        "Dropped" in ev.name for ev in line.events)
            chips.append({"chip": int(m.group(2)), "modules": modules,
                          "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name in span_set:
                        host_spans.append((name, s, e))
                    elif name == window_span:
                        window_found = (s, e)
    chips.sort(key=lambda c: c["chip"])
    # a chip that ran nothing in the trace is not part of the mesh used
    chips = [c for c in chips if c["modules"]]
    if window_ns is None and window_found is not None:
        window_ns = window_found
    if window_ns is None:
        every = [t for c in chips for _n, s, e in c["modules"]
                 for t in (s, e)]
        window_ns = (min(every), max(every)) if every else (0.0, 0.0)
    lo, hi = window_ns
    if dropped and chips:
        hi = min(hi, min(max(e for _n, _s, e in c["modules"])
                         for c in chips))
    out = []
    worst = None
    for c in chips:
        busy, gaps = union_seconds([(s, e) for _n, s, e in c["modules"]],
                                   lo, hi)
        programs: dict = {}
        by_name: dict = {}
        for name, s, e in sorted(c["modules"], key=lambda m: m[1]):
            by_name.setdefault(program_name(name), []).append((s, e))
        for pname, runs in by_name.items():
            for s, e in runs[1:-1]:   # never the trace's first or last
                if lo <= s and e <= hi:
                    programs.setdefault(pname, []).append((e - s) / 1e9)
        ops: dict = {}
        for ev in (c["ops"].events if c["ops"] else ()):
            s = ev.start_ns
            if lo <= s < hi:
                ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
        ops = {short_op(k): v / 1e9 for k, v in ops.items()}
        out.append({"chip": c["chip"], "busy_s": busy,
                    "programs": programs, "ops": ops})
        if worst is None or busy < worst[0]:
            worst = (busy, gaps)
    gaps_by_span: dict = {}
    if worst is not None:
        spans = sorted(host_spans, key=lambda x: x[1])
        for gs, ge in worst[1]:
            mid = (gs + ge) / 2
            owner = idle_label
            for name, s, e in spans:          # sorted by start
                if s > mid:
                    break
                if e >= mid:
                    owner = name              # later start = innermost
            gaps_by_span[owner] = gaps_by_span.get(owner, 0.0) \
                + (ge - gs) / 1e9
    return Reduction(window_s=(hi - lo) / 1e9, chips=out,
                     host_spans=host_spans, gaps_by_span=gaps_by_span,
                     window_ns=(lo, hi), dropped=dropped)


def describe(path: str, max_lines: int = 60) -> str:
    """Planes, lines and event counts of a trace, for looking at one by
    hand (``python benchmark/tools/look_at_trace.py <file>``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names: dict = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:6]
            rows.append(f"  line {line.name!r}: {len(evs)} events; "
                        + ", ".join(f"{k} x{v}" for k, v in top))
            if len(rows) >= max_lines:
                return "\n".join(rows)
    return "\n".join(rows)
