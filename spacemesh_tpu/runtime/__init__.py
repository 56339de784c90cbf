"""One device-job runtime, many tenants (docs/DEVICE_RUNTIME.md).

The four device pipelines — POST init (post/initializer.py), POST prove
(post/prover.py), the verification farm (verify/farm.py) and the k2pow
nonce search (ops/pow.py) — used to each carry a private copy of the
same machinery: bounded in-flight dispatch, donated carry state,
pad-and-trim ragged tails, mesh routing, device-failure
fallback, per-stage spans and metrics.  ROADMAP items #1/#2 (and the
review-fix history in ADVICE.md) argue that class of subtle code should
exist ONCE.  This package is that once:

* :mod:`engine`    — the submit -> batch -> dispatch -> retire executor
  (:class:`engine.Pipeline`): one bounded window of device work in
  flight, early exit, stop, fallback-on-device-failure, per-stage
  spans/metrics with a ``tenant`` label.
* :mod:`queue`     — the async admission primitives the farm's priority
  lanes are built from (:class:`queue.LaneGroup`,
  :class:`queue.KindLanes`): per-lane bounds, backpressure waiters with
  cancellation handoff, in-flight dedup.
* :mod:`workloads` — the registry of device workload kinds (fused init
  labels, packed multi-tenant init, prove scan step, verify batch,
  k2pow) with their warm-shape recipes (tools/warmcache.py compiles
  exactly these).
* :mod:`scheduler` — the multi-tenant layer
  (:class:`scheduler.TenantScheduler`): per-tenant job queues drained
  by fair-share (stride) + deadline admission onto one shared device,
  cross-tenant lane packing for init, gang-scheduled prove windows,
  per-tenant quotas, and a ``tenant`` label flowing through metrics and
  span tracing.
"""

from .engine import Pipeline, PipelineStats, JobStopped  # noqa: F401
from .scheduler import (  # noqa: F401
    JobHandle, SchedulerClosed, TenantScheduler,
)
