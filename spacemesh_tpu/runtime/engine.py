"""The device-job engine: one submit -> batch -> dispatch -> retire loop.

Every device pipeline in this repo has the same steady-state shape: a
host thread enqueues up to K batches of device work (dispatch), then
pops the oldest and blocks on its results (retire), so device compute,
PCIe copies and host-side work overlap.  post/initializer.py,
post/prover.py and ops/pow.py each hand-rolled that deque — and the
prover's reader-error path and the farm's lane waiter each grew
review-fix bugs in their private copies (ADVICE.md; ROADMAP item #2).

:class:`Pipeline` is the one copy.  Workload-specific behavior stays in
two callbacks:

``dispatch(item) -> ticket``
    Enqueue device work for one item and return immediately (the ticket
    is whatever the retire side needs — device arrays, counts, byte
    offsets).  A raised exception is fed to the ``fallback`` hook when
    the caller configured one, before it is allowed to kill the job.
    No POST pipeline configures one: init, prove and k2pow raise on a
    device failure instead of re-running the batch elsewhere unasked.

``retire(ticket) -> result | None``
    Block on the oldest in-flight ticket and consume its results.  A
    non-None return is a sound EARLY EXIT: the pipeline stops pulling
    items, abandons the remaining in-flight tickets (the prover's
    winning-nonce rule) and returns that value.

The engine owns the subtle parts: the bounded window, drain-vs-discard
on stop, early-exit semantics, per-stage wall-time accounting, the
``runtime_*`` metrics and the per-stage spans — all labeled with the
submitting ``tenant`` so a multi-tenant trace decomposes per identity
(docs/DEVICE_RUNTIME.md).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable, Optional

from ..utils import metrics, sanitize, tracing

# per-kind AGGREGATE in-flight depth: concurrent pipelines of one kind
# (two gang prove windows, parallel k2pow searches) each contribute a
# delta instead of clobbering the gauge — the finishing pipeline removes
# only its own share, never zeroes a peer's. Declared shared to the
# lockset sanitizer: every pipeline thread passes through here.
_inflight_lock = sanitize.lock("runtime.engine.inflight")
_inflight_shared = sanitize.SharedField("runtime.engine.inflight_by_kind")
_inflight_by_kind: dict[str, int] = {}


def _inflight_adjust(kind: str, delta: int) -> int:
    with _inflight_lock:
        _inflight_shared.touch()
        n = _inflight_by_kind.get(kind, 0) + delta
        _inflight_by_kind[kind] = n
        return n


class JobStopped(RuntimeError):
    """The job's stop predicate flipped while work was still queued."""


# Sentinel a CONTINUOUS item stream (the multi-tenant packer) yields
# when it has no new work right now: the engine retires the oldest
# in-flight ticket (if any) instead of dispatching, so results keep
# draining while the stream decides whether to block for more work.
# Finite streams (init/prove/pow) never need it — exhausting the
# iterator drains the window.
IDLE = object()


@dataclasses.dataclass
class PipelineStats:
    """Per-run stage accounting (the engine's copy; pipelines fold it
    into their own richer stats objects)."""

    batches: int = 0
    dispatch_s: float = 0.0   # host time enqueueing device work
    retire_s: float = 0.0     # blocked consuming results
    fallbacks: int = 0        # dispatch exceptions absorbed by fallback
    abandoned: int = 0        # dispatched, never retired: dropped on an
    #                           early exit or a stop (the device may
    #                           still run them)
    early_exited: bool = False
    stopped: bool = False

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class Pipeline:
    """Bounded in-flight dispatch->retire executor for one device job.

    ``kind``      workload kind label (metrics/spans): "init", "prove",
                  "pow", "verify", ...
    ``tenant``    identity label carried on every span/metric; "-" for
                  single-tenant embedders.
    ``inflight``  device batches in flight before the oldest retires.
    ``stop``      checked before each dispatch; True discards the
                  remaining in-flight work (the initializer's stop
                  contract: stop latency is one retire, not a drain).
    ``fallback``  ``(item, exc) -> ticket`` — a dispatch exception goes
                  here once per item (device-failure fallback); absent,
                  the exception propagates.
    ``breaker``   an ``obs.remediate.CircuitBreaker`` wrapped around the
                  device-dispatch attempt.  Without one, a permanently
                  dead backend re-pays the failing dispatch on EVERY
                  batch (the pre-remediation behavior: no memory
                  between batches); with one, failures trip it open and
                  dispatch goes straight to ``fallback`` — the item
                  sees a typed :class:`~..obs.remediate.BreakerOpen`
                  instead of the long-dead device error — until a
                  half-open probe finds the device back.
    ``span``      span name prefix; None disables the engine's spans
                  (callers that still own their own, e.g. during
                  migration tests).  Dispatch spans are named
                  ``f"{span}.dispatch"`` so existing timeline tooling
                  (trace-smoke CI, profiler --timeline) keeps matching;
                  the blocking side is ``f"{span}.retire"``.
    ``attrs``     ``item -> dict`` extra dispatch-span attributes.
    ``retire_attrs`` ``ticket -> dict`` extra retire-span attributes.
    ``on_inflight`` depth hook (pipeline-specific gauges).
    """

    def __init__(self, *, kind: str, tenant: str = "-", inflight: int = 3,
                 stop: Optional[Callable[[], bool]] = None,
                 fallback: Optional[Callable[[Any, Exception], Any]] = None,
                 breaker=None,
                 span: str | None = None,
                 attrs: Optional[Callable[[Any], dict]] = None,
                 retire_attrs: Optional[Callable[[Any], dict]] = None,
                 on_inflight: Optional[Callable[[int], None]] = None):
        self.kind = kind
        self.tenant = tenant
        self.inflight = max(int(inflight), 1)
        self._stop = stop
        self._fallback = fallback
        self._breaker = breaker
        self._span = span
        self._attrs = attrs
        self._retire_attrs = retire_attrs
        self._on_inflight = on_inflight
        self.stats = PipelineStats()
        self._pending: deque = deque()
        self._last_depth = 0

    @property
    def pending_count(self) -> int:
        """Tickets in flight right now (continuous streams consult this
        to decide between blocking for work and yielding IDLE)."""
        return len(self._pending)

    # -- internals -----------------------------------------------------

    def _set_inflight(self, n: int) -> None:
        total = _inflight_adjust(self.kind, n - self._last_depth)
        self._last_depth = n
        metrics.runtime_inflight.set(total, kind=self.kind)
        if self._on_inflight is not None:
            self._on_inflight(n)

    def _stage_span(self, stage: str, extra, arg):
        """The ``<span>.<stage>`` span of one item or ticket."""
        if self._span is None:
            return tracing._NOP
        attrs = None
        if tracing.is_enabled():
            attrs = {"kind": self.kind, "tenant": self.tenant}
            if extra is not None:
                attrs.update(extra(arg))
        return tracing.span(f"{self._span}.{stage}", attrs)

    def _dispatch_one(self, dispatch, item):
        t0 = time.perf_counter()
        sp = self._stage_span("dispatch", self._attrs, item)
        br = self._breaker
        with sp:
            if br is not None and not br.allow():
                # open breaker: the device path is known-dead, go
                # straight to the fallback WITHOUT re-paying the
                # failing dispatch attempt (sustained-failure memory
                # between batches)
                from ..obs.remediate import BreakerOpen

                if self._fallback is None:
                    raise BreakerOpen(br.component, br.retry_in())
                ticket = self._fallback(
                    item, BreakerOpen(br.component, br.retry_in()))
                self.stats.fallbacks += 1
                metrics.runtime_fallbacks.inc(kind=self.kind)
            else:
                try:
                    ticket = dispatch(item)
                except Exception as exc:  # noqa: BLE001 — routed to fallback
                    if br is not None:
                        br.record_failure()
                    if self._fallback is None:
                        raise
                    ticket = self._fallback(item, exc)
                    self.stats.fallbacks += 1
                    metrics.runtime_fallbacks.inc(kind=self.kind)
                else:
                    if br is not None:
                        br.record_success()
        self.stats.dispatch_s += time.perf_counter() - t0
        self.stats.batches += 1
        metrics.runtime_dispatched.inc(kind=self.kind, tenant=self.tenant)
        return ticket

    def _abandon(self) -> None:
        """Drop the in-flight tickets unretired, counting them."""
        self.stats.abandoned += len(self._pending)
        self._pending.clear()

    def _retire_one(self, retire, ticket):
        t0 = time.perf_counter()
        try:
            with self._stage_span("retire", self._retire_attrs, ticket):
                return retire(ticket)
        finally:
            self.stats.retire_s += time.perf_counter() - t0
            metrics.runtime_retired.inc(kind=self.kind, tenant=self.tenant)

    # -- the loop ------------------------------------------------------

    def run(self, items: Iterable[Any], dispatch, retire):
        """Drive ``items`` through the bounded window.

        Returns the first non-None retire result (early exit), or None
        when every item retired (or the stop predicate ended the run —
        ``stats.stopped`` distinguishes).  Stage seconds and counters
        accumulate in ``self.stats`` and the ``runtime_*`` metrics.
        """
        stats = self.stats
        pending = self._pending
        result = None
        try:
            for item in items:
                if self._stop is not None and self._stop():
                    stats.stopped = True
                    # stop contract: discard in-flight device work, the
                    # caller persists whatever already retired
                    self._abandon()
                    return None
                if item is IDLE:
                    if pending:
                        result = self._retire_one(retire, pending.popleft())
                        self._set_inflight(len(pending))
                        if result is not None:
                            stats.early_exited = True
                            self._abandon()
                            return result
                    continue
                pending.append(self._dispatch_one(dispatch, item))
                self._set_inflight(len(pending))
                if len(pending) >= self.inflight:
                    result = self._retire_one(retire, pending.popleft())
                    self._set_inflight(len(pending))
                    if result is not None:
                        stats.early_exited = True
                        self._abandon()  # the result is final
                        return result
            while pending:
                if self._stop is not None and self._stop():
                    stats.stopped = True
                    self._abandon()
                    return None
                result = self._retire_one(retire, pending.popleft())
                self._set_inflight(len(pending))
                if result is not None:
                    stats.early_exited = True
                    self._abandon()
                    return result
            return None
        finally:
            self._set_inflight(0)
            for stage, secs in (("dispatch", stats.dispatch_s),
                                ("retire", stats.retire_s)):
                metrics.runtime_stage_seconds.inc(secs, kind=self.kind,
                                                  stage=stage)
