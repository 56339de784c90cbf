"""The traced window: the JAX profiler, the program's own span tracer
bridged into it (``SPACEMESH_TRACE_JAX``: each program span becomes a
``TraceAnnotation`` on the profiler's clock), and one ``bench.window``
annotation that marks the window on that clock.

``open()`` and ``close()`` must be called on one thread (the annotation
is thread-bound). With tracing off both are cheap no-ops apart from the
clock reads, so a driver calls them unconditionally."""

from __future__ import annotations

import threading
import time


def _stop_profiler(trace_dir: str, keep: bool):
    """Stop the profiler and return its ProfileData.

    ``jax.profiler.stop_trace()`` also converts the trace to
    ``trace.json.gz``, which takes about a minute for the millions of
    op events a label program leaves. The session's own ``stop()``
    hands back the serialized XSpace without that; it is reached
    through ``jax._src.profiler``, so fall back to the public call if
    that ever moves."""
    import jax
    from jax.profiler import ProfileData

    try:
        from jax._src import profiler as _jp

        with _jp._profile_state.lock:
            sess = _jp._profile_state.profile_session
            raw = sess.stop()
            _jp._profile_state.reset()
    except (ImportError, AttributeError):
        from . import xplane

        jax.profiler.stop_trace()
        path = xplane.find_xplane(trace_dir)
        return ProfileData.from_file(path) if path else None
    if keep:
        import os

        os.makedirs(trace_dir, exist_ok=True)
        with open(os.path.join(trace_dir, "kept.xplane.pb"), "wb") as f:
            f.write(raw)
    return ProfileData.from_serialized_xspace(raw)


class TraceWindow:
    def __init__(self, enabled: bool, trace_dir, keep: bool = False) -> None:
        self.enabled = enabled
        self.keep = keep        # also leave the .xplane.pb on disk
        self.data = None        # jax.profiler.ProfileData after stop()
        self.trace_dir = str(trace_dir) if enabled else None
        self.t0 = None          # perf_counter at the window's edges
        self.t1 = None
        self._ann = None
        self._started = False
        self.clock0 = None      # CompileClock snapshots at the edges
        self.clock1 = None

    def hold(self, clock, seconds: float, at: float | None = None,
             on_end=None) -> threading.Thread:
        """Start a thread that holds the window: waits until ``at``
        (perf_counter; None = now), then profiler on, the
        ``bench.window`` annotation over exactly ``seconds``, ``on_end()``
        and profiler off, with ``clock`` snapshotted at both edges. The
        profiler goes on AT the window's start, never before it: the
        device's trace buffer holds 6.3 million op events, which is 3.5
        label programs, and a trace started earlier is full before the
        window opens (lib/xplane.py). Join the thread before reading
        anything: it also collects the profiler's data."""
        def body() -> None:
            if at is not None:
                time.sleep(max(at - time.perf_counter(), 0))
            self.clock0 = clock.snapshot()
            self.start()
            self.mark_begin()
            end = (self.t0 if at is None else at) + seconds
            time.sleep(max(end - time.perf_counter(), 0))
            self.mark_end()
            self.clock1 = clock.snapshot()
            if on_end is not None:
                on_end()
            self.stop()

        t = threading.Thread(target=body, name="bench-window", daemon=True)
        t.start()
        return t

    def open(self) -> None:
        self.start()
        self.mark_begin()

    def close(self) -> None:
        self.mark_end()
        self.stop()
        self.finish()

    def start(self) -> None:
        """Profiler and span tracer on (set-up side of the window)."""
        if self.enabled:
            import jax

            from spacemesh_tpu.utils import tracing

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            opts.host_tracer_level = 1       # TraceAnnotations only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            tracing.start(capacity=1 << 18, jax_bridge=True)
            self._started = True

    def mark_begin(self) -> None:
        """The window's first instant; same thread as mark_end."""
        if self.enabled:
            import jax

            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()

    def mark_end(self) -> None:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def stop(self) -> None:
        """Profiler off; its data is kept in ``self.data``."""
        if self.enabled and self._started:
            self._started = False
            self.data = _stop_profiler(self.trace_dir, self.keep)

    def finish(self) -> None:
        """Span tracer off. Called once the system under test has
        drained, not at the window's end: a span still open when the
        tracer stops is never recorded, and the one open across the
        window's end (a 2.8 s fetch) belongs to the window in part."""
        if self.enabled:
            from spacemesh_tpu.utils import tracing

            tracing.stop()

    def spans(self) -> list:
        """The program's spans that overlap the window. ``inside`` says
        whether a span lies wholly inside it (a reader of durations
        takes only those); ``clipped_us`` is the part of it inside the
        window (a reader of shares of the window takes that)."""
        if not self.enabled:
            return []
        from spacemesh_tpu.utils import tracing

        lo, hi = self.t0 * 1e6, self.t1 * 1e6
        out = []
        for ev in tracing.export()["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            end = ev["ts"] + ev["dur"]
            clipped = min(end, hi) - max(ev["ts"], lo)
            if clipped > 0 or (ev["dur"] == 0 and lo <= ev["ts"] <= hi):
                out.append({"name": ev["name"], "ts_us": ev["ts"],
                            "dur_us": ev["dur"], "tid": ev["tid"],
                            "inside": ev["ts"] >= lo and end <= hi,
                            "clipped_us": max(clipped, 0),
                            "args": ev.get("args", {})})
        return out
