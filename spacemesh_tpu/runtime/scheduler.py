"""Fair-share multi-tenant scheduling over the shared device runtime.

BASELINE.json's last config — "16 smeshers x 4 SU sharded across v5e-8"
— needs many identities served by ONE device set.  Per-job ownership
(one Initializer/Prover owning every device for the duration) leaves
the device idle in every host-side gap: session setup/teardown, ragged
tail batches, metadata saves, disk stalls.  The scheduler closes those
gaps by admitting work from every tenant into the same
submit -> batch -> dispatch -> retire engine (runtime/engine.py):

* **Per-tenant queues + fair share.**  Tenants register with a weight;
  quanta (a prove window, a verify batch, a k2pow search, a packed init
  dispatch's lane share) charge the tenant's virtual time by wall cost
  / weight.  The next quantum always goes to the runnable tenant with
  the LEAST virtual time — a flooding tenant cannot starve a light one
  (stride scheduling).
* **Deadline admission.**  A job submitted with ``deadline_s`` is
  lifted ahead of fair-share order once its deadline is within the
  admission slack (EDF among overdue jobs) — the farm's BLOCK-lane
  urgency generalized to whole jobs.
* **Quotas.**  Per-tenant ``max_queued`` (admission bound; submit
  raises :class:`QuotaExceeded`) and ``max_inflight`` (concurrent
  quanta cap) keep one identity from monopolizing the worker pool.
* **Cross-tenant init packing.**  Init jobs do not dispatch per tenant:
  a packer thread composes lanes from MANY tenants' jobs (fair-share
  order) into one fused per-lane-commitment label program
  (ops/scrypt.py supports (8, B) commitment words), keeps ``inflight``
  packs on the device via the engine, splits the fetched bytes back to
  each tenant's store and folds each tenant's VRF minimum on host
  (runtime/workloads.py fold_min_host — bit-identical to the device
  scan).  16 tiny sessions become a handful of full-bucket programs.
  A pack is as wide as the chips hold (:func:`default_pack_lanes`: the
  verifier's lane ceiling rule), so at mainnet N=8192 one tenant's
  lanes fill a pack and packing reduces to fair-share rotation.
* **Per-tenant durable checkpoints.**  Each packed job saves its resume
  metadata during the run under the single-identity rule (the same
  interval constants and the one helper,
  ``post/initializer.save_checkpoint``): the cursor is the tenant's
  fsynced contiguous count, the CRC interval joins the ledger and the
  VRF carry is recorded, so a crash loses at most one interval of
  every identity.
* **Gang-scheduled prove windows.**  One prove window (a whole disk
  pass: every nonce-group step chain of the window) runs as ONE
  quantum on one worker, gated by a ``gang_windows`` semaphore — its
  donated carry states live on device for the duration, so two prove
  windows never interleave their device state beyond the configured
  gang width.
* **Tenant labels everywhere.**  Every span and metric the runtime
  emits for scheduled work carries the tenant id
  (``runtime_tenant_*``, ``runtime.quantum``/``runtime.segment``
  spans), so a multi-tenant trace decomposes per identity.

The scheduler is thread-based and loop-free: embedders without asyncio
(bench, CLI tools, the grpc worker's executor) drive it directly.
"""

from __future__ import annotations

import concurrent.futures
import itertools
import threading
import time
import zlib
from collections import deque
from pathlib import Path

from ..utils import metrics, sanitize, tracing
from . import engine, workloads

_DEADLINE_SLACK_S = 0.05   # jobs due within this window jump fair share


def default_pack_lanes(n: int) -> int:
    """Lanes per packed init program at scrypt ``n`` when the caller
    names none: ``ops/scrypt.lane_ceiling(n)`` on each device
    ``parallel/mesh.auto_mesh`` would shard a pack over, times those
    devices (the rule the verifier's lane tiles follow). 8,192 on one
    v5e chip (or a platform that reports no memory) at N=8192."""
    import jax

    from ..ops import scrypt
    from ..parallel import mesh as pmesh

    per_chip = scrypt.lane_ceiling(n)
    mesh = pmesh.auto_mesh(per_chip * jax.device_count())
    if mesh is None:
        return per_chip
    return mesh.size * scrypt.lane_ceiling(n, mesh.devices.flat)


class SchedulerClosed(RuntimeError):
    """The scheduler was closed while (or before) the job was pending."""


class QuotaExceeded(RuntimeError):
    """The tenant's max_queued admission bound rejected the submit."""


class JobHandle:
    """One submitted job: a concurrent future plus identity/job labels.

    Handles must be consumed: await :meth:`result` (or :meth:`wait`) on
    every path, or :meth:`cancel` in a ``finally`` — the spacecheck
    SC004 pairing rule enforces exactly this shape on package code.
    """

    def __init__(self, scheduler: "TenantScheduler", job_id: str,
                 tenant: str, kind: str):
        self.scheduler = scheduler
        self.id = job_id
        self.tenant = tenant
        self.kind = kind
        self.future: concurrent.futures.Future = concurrent.futures.Future()

    def done(self) -> bool:
        return self.future.done()

    def result(self, timeout: float | None = None):
        return self.future.result(timeout)

    def wait(self, timeout: float | None = None) -> bool:
        concurrent.futures.wait([self.future], timeout=timeout)
        return self.future.done()

    def cancel(self) -> bool:
        """Cancel a queued job (or stop an init job packing further
        lanes).  Running non-init quanta finish their current quantum;
        a cancelled prove job stops at its next window boundary."""
        return self.scheduler._cancel(self)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<JobHandle {self.id} {self.kind}@{self.tenant}>"


class _Tenant:
    __slots__ = ("id", "weight", "max_inflight", "max_queued", "vtime",
                 "running", "jobs", "init_jobs", "queued_jobs")

    def __init__(self, tid: str, weight: float, max_inflight: int,
                 max_queued: int):
        self.id = tid
        self.weight = max(float(weight), 1e-6)
        self.max_inflight = max(int(max_inflight), 1)
        self.max_queued = max(int(max_queued), 1)
        self.vtime = 0.0
        self.running = 0          # worker quanta currently executing
        self.jobs: deque = deque()       # queued worker jobs (FIFO)
        self.init_jobs: deque = deque()  # init jobs with lanes left to pack
        self.queued_jobs = 0      # admission count (all kinds, live jobs)

    def charge(self, seconds: float) -> None:
        self.vtime += seconds / self.weight


class _Job:
    """A worker-pool job: runs as one or more quanta."""

    __slots__ = ("handle", "tenant", "kind", "fn", "deadline", "cancelled",
                 "gang", "abort", "queued_at", "req")

    def __init__(self, handle: JobHandle, tenant: _Tenant, kind: str, fn,
                 deadline: float | None, gang: bool = False, abort=None,
                 req=None):
        # when it (re)entered its tenant's queue, and the submitter's
        # request identifier: the runtime.quantum span's queue_wait_ms
        # and req
        self.queued_at = time.perf_counter()
        self.req = req
        self.handle = handle
        self.tenant = tenant
        self.kind = kind
        # fn() -> ("done", result) | ("continue", None); multi-quantum
        # jobs (prove) return "continue" between windows
        self.fn = fn
        self.deadline = deadline
        self.cancelled = False
        self.gang = gang
        # abort() releases mid-job resources (an open prove session)
        # when the job resolves without completing; never called while
        # a quantum is executing
        self.abort = abort


class _InitJob:
    """A packed init job: lanes are composed by the packer, not a worker."""

    __slots__ = ("handle", "tenant", "store", "meta", "writer", "cw",
                 "total", "next_index", "outstanding", "written",
                 "min_carry", "cancelled", "error", "progress",
                 "finalized", "crc", "saved_at", "saved_labels")

    def __init__(self, handle: JobHandle, tenant: _Tenant, store, meta,
                 writer, cw, progress=None):
        self.handle = handle
        self.tenant = tenant
        self.store = store
        self.meta = meta
        self.writer = writer
        self.cw = cw                       # (8,) u32 commitment words
        self.total = meta.total_labels
        self.next_index = meta.labels_written   # next lane to pack
        self.outstanding = 0               # lanes dispatched, not retired
        self.written = meta.labels_written
        # running CRC32 of the inline-written bytes of the open
        # checkpoint interval (segments retire in ascending-start order
        # per job: next_index is monotone and the engine retires packs
        # FIFO) — each checkpoint appends it to the metadata's ledger
        # and cuts it, so the next reopen's recovery does not roll a
        # verified cursor back to a stale interval
        self.crc = 0
        # the last checkpoint: monotonic time and cursor (the interval
        # triggers of post/initializer.py)
        self.saved_at = time.monotonic()
        self.saved_labels = self.written
        self.min_carry = None              # (u128 value, index) | None
        if meta.vrf_nonce is not None and meta.vrf_nonce_value is not None:
            v = bytes.fromhex(meta.vrf_nonce_value)
            self.min_carry = (int.from_bytes(v, "little"), meta.vrf_nonce)
        self.cancelled = False
        self.error: Exception | None = None
        self.progress = progress
        self.finalized = False

    @property
    def packable(self) -> int:
        return 0 if self.cancelled or self.error else \
            self.total - self.next_index


class TenantScheduler:
    """Many identities, one device runtime (module docstring).

    ``workers``       worker threads for prove/verify/pow/call quanta.
    ``pack_lanes``    target lanes per packed init dispatch (bucketed);
                      None = :func:`default_pack_lanes` of each pack's N.
    ``inflight``      packed init dispatches in flight (engine window).
    ``gang_windows``  prove windows allowed on device concurrently.
    ``writer_threads`` background writer threads per init job (0 =
                      synchronous writes in retire).
    ``time_source``   injectable clock for deadline tests.

    Lifecycle: construct -> (``start`` unless ``autostart``) -> submit —
    always ``unregister_tenant`` / ``close`` in a ``finally`` (SC004).
    """

    def __init__(self, *, workers: int = 2,
                 pack_lanes: int | None = None,
                 inflight: int = 3, gang_windows: int = 1,
                 writer_threads: int = 0,
                 pack_linger_s: float = 0.002,
                 default_weight: float = 1.0,
                 default_max_inflight: int = 4,
                 default_max_queued: int = 256,
                 autostart: bool = True,
                 time_source=time.monotonic):
        from ..ops import scrypt
        from ..post import initializer
        from ..utils import accel

        # compiled pack shapes persist across processes like every other
        # entry point's (utils/accel.py) — a cold 16-tenant start must
        # not pay one serialized compile per pack bucket
        accel.enable_persistent_cache()
        self.pack_lanes = (None if pack_lanes is None
                           else max(scrypt.shape_bucket(int(pack_lanes)), 1))
        self.inflight = max(int(inflight), 1)
        self.writer_threads = int(writer_threads)
        # a packed job's checkpoint triggers: the single-identity
        # Initializer's own
        self.meta_interval_s = initializer.DEFAULT_META_INTERVAL_S
        self.meta_interval_labels = initializer.DEFAULT_META_INTERVAL_LABELS
        self._pack_widths: dict[int, int] = {}  # scrypt n -> lanes
        # the widest packed program dispatched, in lanes (packer thread)
        self.widest_pack = 0
        self.pack_linger_s = max(float(pack_linger_s), 0.0)
        self._defaults = (default_weight, default_max_inflight,
                          default_max_queued)
        self._now = time_source
        self._lock = sanitize.lock("runtime.scheduler")
        self._work = sanitize.condition(  # workers wait here
            "runtime.scheduler.work", self._lock)
        self._pack_work = sanitize.condition(  # packer waits
            "runtime.scheduler.pack_work", self._lock)
        self._idle = sanitize.condition(  # drain() waits
            "runtime.scheduler.idle", self._lock)
        # the tenant tables are DECLARED SHARED to the lockset
        # sanitizer: submitters, workers, the packer and close() all
        # meet here, always under _lock
        self._shared = sanitize.SharedField("runtime.scheduler.tenants")
        self._tenants: dict[str, _Tenant] = {}
        self._jobs: dict[str, object] = {}  # live job id -> job
        self._ids = itertools.count(1)
        self._closed = False
        self._live_quanta = 0
        self._lane_cost_ema = 1e-4  # seconds per packed init lane
        self._gang = threading.Semaphore(max(int(gang_windows), 1))
        self._workers = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name=f"runtime-worker-{i}")
            for i in range(max(int(workers), 1))]
        self._packer = threading.Thread(target=self._packer_loop,
                                        daemon=True, name="runtime-packer")
        self._started = False
        if autostart:
            self.start()

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        for t in self._workers:
            t.start()
        self._packer.start()

    def close(self) -> None:
        """Stop the pool; queued jobs fail with SchedulerClosed.  Safe
        to call twice.  Running quanta finish (they hold device state
        mid-flight) and their jobs then resolve as closed."""
        with self._lock:
            self._shared.touch()
            if self._closed:
                return
            self._closed = True
            failed: list = []
            for t in self._tenants.values():
                failed.extend(t.jobs)
                t.jobs.clear()
                t.init_jobs.clear()
            self._work.notify_all()
            self._pack_work.notify_all()
        for job in failed:
            self._resolve(job, error=SchedulerClosed("scheduler closed"))
        if self._started:
            for t in self._workers:
                t.join(timeout=30)
            self._packer.join(timeout=30)
        # no thread touches jobs past this point: finalize whatever the
        # packer abandoned mid-flight (writers drained+closed, futures
        # failed) so close() never strands a handle unresolved
        with self._lock:
            self._shared.touch(write=False)
            leftovers = list(self._jobs.values())
        closed_exc = SchedulerClosed("scheduler closed")
        for job in leftovers:
            if isinstance(job, _InitJob):
                job.error = job.error or closed_exc
                self._finalize_init(job)
            else:
                self._resolve(job, error=closed_exc)

    def __enter__(self) -> "TenantScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every submitted job resolved; False on timeout."""
        deadline = None if timeout is None else self._now() + timeout
        with self._idle:
            self._shared.touch(write=False)
            while self._jobs:
                left = None if deadline is None else deadline - self._now()
                if left is not None and left <= 0:
                    return False
                self._idle.wait(left if left is not None else 1.0)
        return True

    # -- tenants -------------------------------------------------------

    def register_tenant(self, tid: str, *, weight: float | None = None,
                        max_inflight: int | None = None,
                        max_queued: int | None = None) -> str:
        """Register (or re-weight) a tenant; pair with
        :meth:`unregister_tenant` when the identity goes away."""
        dw, di, dq = self._defaults
        with self._lock:
            self._shared.touch()
            t = self._tenants.get(tid)
            if t is None:
                t = self._tenants[tid] = _Tenant(
                    tid, weight if weight is not None else dw,
                    max_inflight if max_inflight is not None else di,
                    max_queued if max_queued is not None else dq)
                # a new tenant starts at the LEADING edge of virtual
                # time, not 0 — or it would owe the whole backlog of
                # every long-running tenant and stall them on arrival
                live = [x.vtime for x in self._tenants.values() if x is not t]
                t.vtime = min(live) if live else 0.0
            else:
                if weight is not None:
                    t.weight = max(float(weight), 1e-6)
                if max_inflight is not None:
                    t.max_inflight = max(int(max_inflight), 1)
                if max_queued is not None:
                    t.max_queued = max(int(max_queued), 1)
        return tid

    def unregister_tenant(self, tid: str) -> None:
        """Drop a tenant; its queued jobs fail with SchedulerClosed and
        its per-tenant gauge series disappear from the scrape (a gone
        identity must not pin a stale series — the PR 7 lesson)."""
        exc = SchedulerClosed(f"tenant {tid} unregistered")
        with self._lock:
            self._shared.touch()
            t = self._tenants.pop(tid, None)
            if t is None:
                return
            failed = list(t.jobs)
            failed_inits = []
            for ij in t.init_jobs:
                if ij.outstanding == 0:
                    failed_inits.append(ij)
                else:
                    # lanes still in flight: mark the job so the
                    # packer's retire finalizes (and resolves) it when
                    # they land — clearing it silently would strand the
                    # handle forever
                    ij.error = ij.error or exc
            t.jobs.clear()
            t.init_jobs.clear()
        metrics.runtime_tenant_queued.remove(tenant=tid)
        # counters/histograms carrying the tenant beside other labels
        # drop ALL of that tenant's series too (a churn of short-lived
        # identities — verifyd clients — must not grow the registry
        # without bound; the queued-gauge removal alone left these)
        for inst in (metrics.runtime_tenant_jobs,
                     metrics.runtime_tenant_labels,
                     metrics.runtime_tenant_checkpoints,
                     metrics.runtime_quantum_seconds):
            inst.remove_matching(tenant=tid)
        for job in failed:
            self._resolve(job, error=exc)
        for job in failed_inits:
            # through finalize, not a bare resolve: the job's writer
            # threads and store fds must close with it
            job.error = job.error or exc
            self._finalize_init(job)

    def tenants(self) -> list[str]:
        with self._lock:
            self._shared.touch(write=False)
            return sorted(self._tenants)

    def namespaced(self, shard: str) -> "ShardScheduler":
        """A tenant-id-namespacing view for one fleet shard: every
        tenant registered through it lives as ``<shard>/<tid>``, so N
        verifyd replicas can share ONE device runtime without their
        client identities (fair-share vtime, quotas, per-tenant metric
        series) colliding (verifyd/fleet.py)."""
        return ShardScheduler(self, shard)

    # -- submission ----------------------------------------------------

    # guarded by: self._lock — every submit_* caller enters with the scheduler lock held
    def _admit(self, tid: str, kind: str) -> tuple[_Tenant, JobHandle]:
        self._shared.touch()
        if self._closed:
            raise SchedulerClosed("scheduler closed")
        t = self._tenants.get(tid)
        if t is None:
            raise KeyError(f"tenant {tid!r} is not registered")
        if t.queued_jobs >= t.max_queued:
            metrics.runtime_tenant_jobs.inc(tenant=tid, kind=kind,
                                            state="rejected")
            raise QuotaExceeded(
                f"tenant {tid}: {t.queued_jobs} jobs queued >= "
                f"max_queued {t.max_queued}")
        handle = JobHandle(self, f"{kind}-{next(self._ids)}", tid, kind)
        t.queued_jobs += 1
        metrics.runtime_tenant_queued.set(t.queued_jobs, tenant=tid)
        return t, handle

    def submit_call(self, tid: str, fn, *, kind: str = "call",
                    deadline_s: float | None = None,
                    req=None) -> JobHandle:
        """Generic single-quantum job: ``fn()`` runs on a worker; its
        return value resolves the handle.  ``req`` is the submitter's
        request identifier, recorded on the ``runtime.quantum`` span."""
        with self._lock:
            t, handle = self._admit(tid, kind)
            job = _Job(handle, t, kind,
                       lambda: ("done", fn()),
                       None if deadline_s is None
                       else self._now() + deadline_s, req=req)
            self._jobs[handle.id] = job
            t.jobs.append(job)
            self._work.notify()
        return handle

    def submit_pow(self, tid: str, challenge: bytes, node_id: bytes,
                   difficulty: bytes, *, deadline_s: float | None = None,
                   **search_opts) -> JobHandle:
        """k2pow nonce search as a scheduled quantum (ops/pow.py)."""
        from ..ops import pow as k2pow

        return self.submit_call(
            tid, lambda: k2pow.search(challenge, node_id, difficulty,
                                      tenant=tid, **search_opts),
            kind="k2pow", deadline_s=deadline_s)

    def submit_verify(self, tid: str, items: list, params=None, *,
                      seed: bytes | None = None,
                      deadline_s: float | None = None) -> JobHandle:
        """One batched POST verification (post/verifier.verify_many)
        as a scheduled quantum; resolves to the per-item bool list."""
        from ..post import verifier as post_verifier

        return self.submit_call(
            tid, lambda: post_verifier.verify_many(items, params, seed=seed),
            kind="verify", deadline_s=deadline_s)

    def submit_prove(self, tid: str, data_dir, challenge: bytes,
                     params=None, *, deadline_s: float | None = None,
                     **prover_opts) -> JobHandle:
        """A full prove as a multi-quantum job: the k2pow gate is one
        quantum, then each nonce window is one GANG quantum (one disk
        pass, never interleaved with another tenant's window beyond the
        configured gang width).  Resolves to the Proof."""
        from ..post.prover import Prover

        state: dict = {}

        def quantum():
            if "session" not in state:
                prover = Prover(data_dir, params, **prover_opts)
                state["session"] = prover.session(challenge, tenant=tid)
                return "continue", None
            session = state["session"]
            try:
                proof = session.step()
            except Exception:
                session.close()
                raise
            if proof is None:
                return "continue", None
            session.close()
            return "done", proof

        def abort():
            session = state.pop("session", None)
            if session is not None:
                session.close()

        with self._lock:
            t, handle = self._admit(tid, "prove")
            job = _Job(handle, t, "prove", quantum,
                       None if deadline_s is None
                       else self._now() + deadline_s, gang=True,
                       abort=abort)
            self._jobs[handle.id] = job
            t.jobs.append(job)
            self._work.notify()
        return handle

    def submit_init(self, tid: str, data_dir, *, node_id: bytes,
                    commitment: bytes, num_units: int, labels_per_unit: int,
                    scrypt_n: int = 8192,
                    max_file_size: int = 64 * 1024 * 1024,
                    progress=None, fs=None) -> JobHandle:
        """Create-or-resume one identity's POST init as a PACKED job:
        its lanes dispatch interleaved with every other tenant's through
        the shared engine.  Resolves to the final PostMetadata.  ``fs``
        is the injectable I/O layer (post/faultfs.py fault plans)."""
        from ..ops import scrypt
        from ..post.data import LabelStore
        from ..post.initializer import open_or_create_meta

        meta = open_or_create_meta(
            Path(data_dir), node_id=node_id, commitment=commitment,
            num_units=num_units, labels_per_unit=labels_per_unit,
            scrypt_n=scrypt_n, max_file_size=max_file_size, fs=fs)
        store = LabelStore(data_dir, meta, fs=fs)
        cw = scrypt.commitment_to_words(commitment)
        try:
            with self._lock:
                t, handle = self._admit(tid, "init")
                writer = (store.start_writer(self.writer_threads,
                                             queue_depth=8)
                          if self.writer_threads > 0 else None)
                job = _InitJob(handle, t, store, meta, writer, cw,
                               progress=progress)
                self._jobs[handle.id] = job
                if job.packable > 0:
                    t.init_jobs.append(job)
                    self._pack_work.notify()
                else:
                    # nothing to do (already complete): resolve now
                    self._jobs.pop(handle.id, None)
                    t.queued_jobs -= 1
                    handle.future.set_result(meta)
                    metrics.runtime_tenant_jobs.inc(tenant=tid, kind="init",
                                                    state="done")
        except Exception:
            store.close()
            raise
        return handle

    # -- cancellation / resolution -------------------------------------

    def _cancel(self, handle: JobHandle) -> bool:
        with self._lock:
            self._shared.touch()
            job = self._jobs.get(handle.id)
            if job is None:
                return False
            if isinstance(job, _InitJob):
                job.cancelled = True
                try:
                    job.tenant.init_jobs.remove(job)
                except ValueError:
                    pass
                if job.outstanding > 0:
                    return True  # packer finalizes after in-flight retires
            else:
                job.cancelled = True
                try:
                    job.tenant.jobs.remove(job)
                except ValueError:
                    return True  # running: stops at its next quantum edge
        if isinstance(job, _InitJob):
            # through finalize: writer threads and store fds close too
            self._finalize_init(job)
        else:
            self._resolve(job, cancelled=True)
        return True

    def _resolve(self, job, result=None, error: Exception | None = None,
                 cancelled: bool = False) -> None:
        handle = job.handle
        with self._lock:
            self._shared.touch()
            if self._jobs.pop(handle.id, None) is None:
                return  # already resolved
            t = self._tenants.get(handle.tenant)
            if t is not None:
                t.queued_jobs -= 1
                metrics.runtime_tenant_queued.set(t.queued_jobs,
                                                  tenant=t.id)
            self._idle.notify_all()
        state = ("cancelled" if cancelled
                 else "failed" if error is not None else "done")
        metrics.runtime_tenant_jobs.inc(tenant=handle.tenant,
                                        kind=handle.kind, state=state)
        if state != "done" and isinstance(job, _Job) \
                and job.abort is not None:
            try:
                job.abort()
            except Exception:  # noqa: BLE001 — cleanup must not mask the outcome
                pass
        if cancelled:
            handle.future.cancel()
            # wakes JobHandle.wait() too (concurrent.futures.wait
            # sees a cancel only once it is notified)
            handle.future.set_running_or_notify_cancel()
        elif error is not None:
            handle.future.set_exception(error)
        else:
            handle.future.set_result(result)

    # -- worker pool (prove/verify/pow/call quanta) ---------------------

    # guarded by: self._lock — _worker_loop picks with the scheduler lock held
    def _pick_job(self) -> _Job | None:
        """Under the lock: the next quantum by deadline-then-fair-share."""
        self._shared.touch()
        now = self._now()
        best_t = None
        overdue_job = None
        overdue_deadline = None
        for t in self._tenants.values():
            if not t.jobs or t.running >= t.max_inflight:
                continue
            for job in t.jobs:
                if job.deadline is not None \
                        and job.deadline <= now + _DEADLINE_SLACK_S \
                        and (overdue_deadline is None
                             or job.deadline < overdue_deadline):
                    overdue_job, overdue_deadline = job, job.deadline
            if best_t is None or t.vtime < best_t.vtime:
                best_t = t
        if best_t is None:
            return None
        fair_pick = best_t.jobs[0]
        if overdue_job is not None:
            if overdue_job is not fair_pick:
                metrics.runtime_deadline_boosts.inc()
            overdue_job.tenant.jobs.remove(overdue_job)
            return overdue_job
        return best_t.jobs.popleft()

    def _worker_loop(self) -> None:
        while True:
            with self._lock:
                self._shared.touch(write=False)
                job = None
                while not self._closed:
                    job = self._pick_job()
                    if job is not None:
                        break
                    self._work.wait()
                if job is None:  # closed
                    return
                job.tenant.running += 1
                self._live_quanta += 1
            self._run_quantum(job)

    def _run_quantum(self, job: _Job) -> None:
        t0 = time.perf_counter()
        outcome, result, error = "continue", None, None
        # gang gating applies to every quantum of a gang job (the pow
        # gate is cheap; per-window discrimination is not worth a
        # second state channel)
        if job.gang:
            self._gang.acquire()
        attrs = None
        if tracing.is_enabled():
            attrs = {"tenant": job.tenant.id, "kind": job.kind,
                     "job": job.handle.id,
                     "queue_wait_ms": round(
                         (t0 - job.queued_at) * 1e3, 3)}
            if job.req is not None:
                attrs["req"] = job.req
        try:
            with tracing.span("runtime.quantum", attrs):
                try:
                    outcome, result = job.fn()
                except Exception as exc:  # noqa: BLE001 — job fails, pool survives
                    outcome, error = "error", exc
        finally:
            if job.gang:
                self._gang.release()
            dt = time.perf_counter() - t0
            metrics.runtime_quantum_seconds.inc(dt, kind=job.kind,
                                                tenant=job.tenant.id)
            with self._lock:
                self._shared.touch()
                job.tenant.charge(dt)
                job.tenant.running -= 1
                self._live_quanta -= 1
                requeue = (outcome == "continue" and error is None
                           and not job.cancelled and not self._closed)
                if requeue:
                    # multi-quantum job continues ahead of the tenant's
                    # own later jobs (per-job FIFO), fair share decides
                    # across tenants
                    job.queued_at = time.perf_counter()
                    job.tenant.jobs.appendleft(job)
                self._work.notify()
            if error is not None:
                self._resolve(job, error=error)
            elif job.cancelled:
                self._resolve(job, cancelled=True)
            elif outcome == "done":
                self._resolve(job, result=result)
            elif not requeue:
                # dropped at close mid-job: the handle must not strand
                self._resolve(job, error=SchedulerClosed(
                    "scheduler closed"))

    # -- the init packer ------------------------------------------------

    def pack_width(self, n: int) -> int:
        """Lanes the packer composes a pack of scrypt ``n`` to."""
        with self._lock:
            self._shared.touch(write=False)
            return self._width_locked(n)

    # guarded by: self._lock — pack_width and _compose_pack hold it
    def _width_locked(self, n: int) -> int:
        if self.pack_lanes is not None:
            return self.pack_lanes
        width = self._pack_widths.get(n)
        if width is None:
            width = self._pack_widths[n] = default_pack_lanes(n)
        return width

    # guarded by: self._lock — _compose_pack calls it with the lock held
    def _packable_locked(self):
        """-> (tenants with init work, the N of the next pack, its width,
        packable lanes of that N)."""
        ready = [t for t in self._tenants.values() if t.init_jobs]
        if not ready:
            return ready, None, 0, 0
        n = min(ready, key=lambda t: t.vtime).init_jobs[0].meta.scrypt_n
        avail = sum(j.packable for t in ready for j in t.init_jobs
                    if j.meta.scrypt_n == n)
        return ready, n, self._width_locked(n), avail

    def _compose_pack(self, block: bool):
        """Cut one pack of init lanes in fair-share order.

        ``block`` — wait for work (the engine window is empty); False
        returns None immediately when no tenant has packable lanes (the
        packer then yields IDLE so in-flight packs keep retiring).
        Returns (segments, scrypt_n), or None on close/no-work.

        One static N per fused program: the least-vtime tenant's head
        job sets it, and with it the pack's width (:meth:`pack_width`).

        Pack-fill policy: a burst of submits races the packer, and a
        half-empty first pack both wastes lanes and mints a smaller
        shape bucket.  So a partial pack LINGERS up to
        ``pack_linger_s`` for more lanes to arrive, and with work
        already in flight (``block`` False) a pack under half full is
        deferred outright — the engine retires results meanwhile and
        the lanes coalesce into the next full pack."""
        work = self._packable_locked
        with self._lock:
            self._shared.touch()
            while True:
                if self._closed:
                    return None
                ready, n, width, avail = work()
                if ready:
                    if avail >= width:
                        break
                    if not block:
                        if avail >= width // 2:
                            break
                        return None
                    deadline = time.monotonic() + self.pack_linger_s
                    while avail < width and not self._closed:
                        left = deadline - time.monotonic()
                        if left <= 0 or not self._pack_work.wait(left):
                            break
                        ready, n, width, avail = work()
                    ready, n, width, avail = work()
                    if ready:
                        break
                    continue
                if not block:
                    return None
                self._pack_work.wait()
            segments: list[workloads.PackSegment] = []
            lanes = 0
            for t in sorted(ready, key=lambda t: t.vtime):
                while t.init_jobs and lanes < width:
                    job = t.init_jobs[0]
                    take = min(job.packable, width - lanes)
                    if take == 0:
                        # cancelled/errored (packable 0) or the pack is
                        # full for this tenant's head job: never emit a
                        # zero-count segment
                        if job.packable == 0:
                            t.init_jobs.popleft()
                            continue
                        break
                    if job.meta.scrypt_n != n:
                        break  # one static N per fused program
                    segments.append(workloads.PackSegment(
                        job, job.next_index, take, lanes))
                    job.next_index += take
                    job.outstanding += take
                    lanes += take
                    # provisional fair-share charge at the EMA lane cost
                    # (the true wall cost lands in the EMA at retire)
                    t.charge(take * self._lane_cost_ema)
                    if job.packable == 0:
                        t.init_jobs.popleft()
                if lanes >= width:
                    break
            return segments, n

    def _dispatch_pack(self, pack):
        import jax.numpy as jnp
        import numpy as np

        from ..ops import scrypt
        from ..parallel import mesh as pmesh

        segments, n = pack
        lanes = sum(s.count for s in segments)
        cw = np.empty((8, lanes), dtype=np.uint32)
        idx = np.empty(lanes, dtype=np.uint64)
        for s in segments:
            cw[:, s.lane0:s.lane0 + s.count] = s.job.cw[:, None]
            idx[s.lane0:s.lane0 + s.count] = np.arange(
                s.start, s.start + s.count, dtype=np.uint64)
        metrics.runtime_pack_occupancy.observe(lanes)
        metrics.runtime_pack_tenants.observe(
            len({s.job.tenant.id for s in segments}))
        # the mesh rule every entry point shares (parallel/mesh.py
        # auto_mesh). Packs dispatch at their shape bucket either way —
        # one executable per (n, bucket) — so the bucket is what the
        # mesh must divide.
        bucket = scrypt.shape_bucket(lanes)
        self.widest_pack = max(self.widest_pack, bucket)
        mesh = pmesh.auto_mesh(bucket)
        if mesh is not None:
            # mesh callers pre-bucket on host (ops/scrypt.py skips its
            # eager pad for sharded inputs): repeat the last lane —
            # a real commitment/index, so padding lanes recompute a real
            # label and stay branch-free; _retire_pack slices only the
            # segment-addressed lanes
            if bucket != lanes:
                cw = np.concatenate(
                    [cw, np.repeat(cw[:, -1:], bucket - lanes, axis=1)],
                    axis=1)
                idx = np.concatenate(
                    [idx, np.repeat(idx[-1:], bucket - lanes)])
            lo, hi = scrypt.split_indices(idx)
            words = pmesh.scrypt_labels_sharded(mesh, cw, lo, hi, n=n)
        else:
            lo, hi = scrypt.split_indices(idx)
            # scrypt_labels_jit pads ragged packs to their shape bucket
            # (per-lane cw padded too) — one executable per (n, bucket)
            words = scrypt.scrypt_labels_jit(
                jnp.asarray(cw), jnp.asarray(lo), jnp.asarray(hi), n=n)
        return words, segments, time.perf_counter()

    def _retire_pack(self, ticket) -> None:
        import numpy as np

        from ..ops import scrypt

        words, segments, t_dispatch = ticket
        arr = np.asarray(words)  # the only device sync of the pack
        lanes = sum(s.count for s in segments)
        dt = time.perf_counter() - t_dispatch
        # EMA of the measured per-lane cost feeds the provisional
        # fair-share charge in _compose_pack — which reads it under the
        # scheduler lock, so the read-modify-write must hold it too or
        # a concurrent compose can consume (and charge tenants by) a
        # half-updated cost (found by SC007, ISSUE 12)
        with self._lock:
            self._shared.touch()
            self._lane_cost_ema += 0.25 * (dt / max(lanes, 1)
                                           - self._lane_cost_ema)
        # ONE byte conversion for the whole pack, sliced per segment —
        # 16 tiny per-tenant byteswaps would hand back the per-call
        # overhead the pack just amortized
        pack_bytes = scrypt.labels_to_bytes(arr)
        for s in segments:
            job: _InitJob = s.job
            with tracing.span("runtime.segment",
                              {"tenant": job.tenant.id, "start": s.start,
                               "count": s.count}
                              if tracing.is_enabled() else None):
                try:
                    if job.error is None and not job.cancelled:
                        data = pack_bytes[s.lane0 * scrypt.LABEL_BYTES:
                                          (s.lane0 + s.count)
                                          * scrypt.LABEL_BYTES]
                        if job.writer is not None:
                            job.writer.submit(s.start, data)
                        else:
                            job.store.write_labels(s.start, data)
                            job.crc = zlib.crc32(data, job.crc)
                        job.min_carry = workloads.fold_min_host(
                            job.min_carry, data, s.start)
                        job.written = max(job.written, s.start + s.count)
                        metrics.runtime_tenant_labels.inc(
                            s.count, tenant=job.tenant.id)
                        if job.progress is not None:
                            job.progress(job.written, job.total)
                except Exception as exc:  # noqa: BLE001 — fail THIS job, not the pack
                    job.error = exc
        # interval checkpoints, while the segments' lanes still count as
        # outstanding: a cancel or unregister on another thread then
        # leaves the job's finalize to this thread instead of racing
        # the save (a job's last lanes checkpoint in finalize)
        for s in segments:
            job = s.job
            if (job.error is None and not job.cancelled
                    and job.written < job.total
                    and (time.monotonic() - job.saved_at
                         >= self.meta_interval_s
                         or job.written - job.saved_labels
                         >= self.meta_interval_labels)):
                try:
                    self._checkpoint(job)
                except Exception as exc:  # noqa: BLE001 — fail THIS job, not the pack
                    job.error = exc
        finalize: list[_InitJob] = []
        for s in segments:
            job = s.job
            with self._lock:
                self._shared.touch()
                job.outstanding -= s.count
                if job.error is not None or job.cancelled:
                    # packable is 0 now: drop the queued remainder so
                    # the compose loop stops seeing this tenant as
                    # ready work
                    try:
                        job.tenant.init_jobs.remove(job)
                    except ValueError:
                        pass
                done = (job.outstanding == 0
                        and (job.next_index >= job.total or job.cancelled
                             or job.error is not None))
            if done and job not in finalize:
                finalize.append(job)
        for job in finalize:
            self._finalize_init(job)

    def _checkpoint(self, job: _InitJob) -> None:
        """Persist one packed job's resume metadata: the single-identity
        ordering rule through its one helper
        (``post/initializer.save_checkpoint``)."""
        from ..post.initializer import save_checkpoint

        if job.writer is not None:
            cut = job.writer.checkpoint
        else:
            def cut():
                # same contract as the writer pool's checkpoint(): fsync
                # first, then hand back the cursor and cut the interval
                job.store.sync()
                crc, job.crc = job.crc, 0
                return job.written, crc
        nonce, value = workloads.min_carry_to_meta(job.min_carry)
        with tracing.span("runtime.checkpoint",
                          {"tenant": job.tenant.id}
                          if tracing.is_enabled() else None) as sp:
            durable = save_checkpoint(job.meta, job.store, cut,
                                      vrf_nonce=nonce, vrf_value=value,
                                      writer=job.writer)
            sp.set(durable=durable)
        job.saved_at = time.monotonic()
        job.saved_labels = job.written
        metrics.runtime_tenant_checkpoints.inc(tenant=job.tenant.id)

    def _finalize_init(self, job: _InitJob) -> None:
        # idempotent: unregister/close/retire can race to finalize the
        # same job; only the first pass drains/closes and resolves
        with self._lock:
            self._shared.touch()
            if job.finalized:
                return
            job.finalized = True
        error = job.error
        try:
            # a job that completed or was cancelled (a stop) checkpoints
            # what is durable; a failed one keeps its last checkpoint
            # (what a crash at that instant would have left)
            if error is None:
                if job.writer is not None:
                    job.writer.drain()
                self._checkpoint(job)
        except BaseException as exc:  # noqa: BLE001 — surface via the handle
            error = exc
        finally:
            if job.writer is not None:
                job.writer.close(drain=False)
            job.store.close()
        if job.cancelled and error is None:
            self._resolve(job, cancelled=True)
        elif error is not None:
            self._resolve(job, error=error)
        else:
            self._resolve(job, result=job.meta)
        if error is not None and not isinstance(error, Exception) \
                and error is not job.error:
            raise error  # a power cut (post/faultfs) ends its thread too

    def _packer_loop(self) -> None:
        """The shared-device init stream: one engine pipeline whose
        items are cross-tenant packs, kept ``inflight`` deep for the
        whole life of the scheduler — tenant boundaries never drain the
        device the way per-job ownership does."""
        pipe = engine.Pipeline(kind="init_pack", tenant="*",
                               inflight=self.inflight, span="runtime.pack",
                               attrs=self._pack_attrs,
                               stop=lambda: self._closed)  # spacecheck: ok=SC007 monotonic close flag; a stale read only delays stop by one batch

        def packs():
            while True:
                if self._closed:
                    return
                # block for work only when the window is empty: with
                # packs in flight, an empty queue yields IDLE so the
                # engine retires results instead of deadlocking a full
                # window behind a quiet submit queue
                pack = self._compose_pack(block=pipe.pending_count == 0)
                if pack is None or not pack[0]:
                    if self._closed:
                        return
                    if pipe.pending_count:
                        yield engine.IDLE
                    continue
                yield pack

        try:
            pipe.run(packs(), self._dispatch_pack, self._retire_pack)
        except BaseException as exc:  # noqa: BLE001 — the power cut of post/faultfs too
            # the pipeline is gone and nothing in flight will retire:
            # fail every init job with what stopped it, keeping each
            # one's last checkpoint (as a crash here would)
            with self._lock:
                self._shared.touch(write=False)
                jobs = [j for j in self._jobs.values()
                        if isinstance(j, _InitJob)]
            for j in jobs:
                j.error = j.error or exc
                self._finalize_init(j)
            if not isinstance(exc, Exception):
                raise

    @staticmethod
    def _pack_attrs(pack) -> dict:
        """``runtime.pack.dispatch`` attributes: real ``lanes``, distinct
        ``tenants`` and the ``bucket`` the program runs at."""
        from ..ops import scrypt

        lanes = sum(s.count for s in pack[0])
        return {"lanes": lanes,
                "tenants": len({s.job.tenant.id for s in pack[0]}),
                "bucket": scrypt.shape_bucket(lanes)}


class ShardScheduler:
    """One shard's view of a shared :class:`TenantScheduler`.

    Prefixes every tenant id with ``<shard>/`` on the way in and strips
    it on the way out, so per-shard client registries (verifyd fleet
    replicas) scale past one registry's identity space while sharing
    the device runtime.  ``close``/``drain``/``start`` pass through to
    the underlying scheduler — the OWNER decides lifetime; a view held
    by a non-owning service simply never calls close (the same
    ownership rule VerifydService already applies to an injected
    scheduler).
    """

    def __init__(self, inner: TenantScheduler, shard: str):
        self.inner = inner
        self.shard = str(shard)
        self._prefix = f"{self.shard}/"

    def _tid(self, tid: str) -> str:
        return self._prefix + str(tid)

    def register_tenant(self, tid: str, **kwargs) -> str:
        self.inner.register_tenant(self._tid(tid), **kwargs)
        return str(tid)

    def unregister_tenant(self, tid: str) -> None:
        self.inner.unregister_tenant(self._tid(tid))

    def submit_call(self, tid: str, fn, **kwargs) -> JobHandle:
        return self.inner.submit_call(self._tid(tid), fn, **kwargs)

    def tenants(self) -> list[str]:
        return [t[len(self._prefix):] for t in self.inner.tenants()
                if t.startswith(self._prefix)]

    def start(self) -> None:
        self.inner.start()

    def drain(self, timeout: float | None = None) -> bool:
        return self.inner.drain(timeout)

    def close(self) -> None:
        self.inner.close()
