"""POST initialization: fill the data directory with scrypt labels.

The PostSetupManager equivalent (reference activation/post.go:185-449 drives
CGo `initialization.Initialize`; here the labeler is the JAX kernel in
ops/scrypt.py). The init loop is a streaming pipeline with three decoupled
stages (docs/POST_PIPELINE.md):

  dispatch  — enqueue up to K label batches on the accelerator, each chained
              to an on-device LE-u128 argmin that folds the batch into a
              donated running-minimum carry (the VRF-nonce scan; no host
              lexsort on the per-batch path);
  fetch     — pop the oldest in-flight batch, copy its bytes to host (this
              is the only per-batch device sync), per-shard when the batch
              was sharded over a device mesh;
  write     — hand the bytes to a bounded-queue background writer pool
              (post/data.py LabelWriter), so disk, PCIe and compute overlap.

The bounded-window dispatch/retire machinery itself lives in the shared
device-job runtime (spacemesh_tpu/runtime/engine.py Pipeline) — this
module only supplies the init-specific dispatch and retire callbacks;
the multi-tenant scheduler (runtime/scheduler.py) serves many
identities' inits through the same engine with cross-tenant lane
packing.

Resume metadata is rewritten on a time/label interval rather than per
batch, with one ordering rule: the persisted ``labels_written`` cursor is
the writer pool's *durable* cursor (contiguous bytes on disk), never the
dispatch frontier. The VRF scan may run ahead of the cursor — that is safe
because labels are deterministic: resume recomputes them and the min-merge
is idempotent.

When more than one device is visible, batches route through
parallel/mesh.py (data-parallel lane sharding) and each device shard's
bytes are striped to the writer pool independently.

Progress/status mirrors the reference's state machine
(NotStarted/InProgress/Complete — activation/post.go:128-137).
"""

from __future__ import annotations

import dataclasses
import enum
import errno
import os
import sys
import time
from pathlib import Path
from typing import Callable

import jax.numpy as jnp
import numpy as np

from ..ops import scrypt
from ..runtime import engine
from ..utils import metrics, tracing
from .data import LabelStore, LabelWriter, PostMetadata

DEFAULT_BATCH = 1 << 13  # 8192 labels = 8 MiB ROMix scratch per 1k... tuned in bench
DEFAULT_INFLIGHT = 3     # device batches in flight before the oldest is fetched
DEFAULT_WRITERS = 2      # background writer threads
DEFAULT_WRITER_QUEUE = 8  # pending writes before dispatch backpressure
DEFAULT_META_INTERVAL_S = 5.0
DEFAULT_META_INTERVAL_LABELS = 1 << 20


class Status(enum.Enum):
    NOT_STARTED = "not_started"
    IN_PROGRESS = "in_progress"
    COMPLETE = "complete"
    STOPPED = "stopped"
    ERROR = "error"


@dataclasses.dataclass
class PipelineStats:
    """Host-side per-stage accounting for one run (tools/profiler.py
    --pipeline dumps this; the same numbers feed utils/metrics.py)."""

    batches: int = 0
    shards: int = 0
    dispatch_s: float = 0.0   # host time spent enqueueing device work
    fetch_s: float = 0.0      # blocked on device->host label copies
    write_stall_s: float = 0.0  # blocked on writer-pool backpressure
    write_s: float = 0.0      # filesystem time inside the writer pool
    save_s: float = 0.0       # metadata rewrites
    meta_saves: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class InitResult:
    labels_written: int
    vrf_nonce: int
    elapsed_s: float
    labels_per_s: float
    stats: PipelineStats | None = None


class Initializer:
    """Fills (or resumes) one identity's POST data directory."""

    # ``progress(done, total)`` reports the FETCH frontier — labels whose
    # bytes reached the host and were handed to the writer pool. Up to the
    # writer queue may still be in flight to disk; the durable cursor is
    # what metadata persists (docs/POST_PIPELINE.md ordering rule).
    def __init__(self, data_dir: str | Path, meta: PostMetadata,
                 batch_size: int = DEFAULT_BATCH,
                 progress: Callable[[int, int], None] | None = None,
                 inflight: int | None = None,
                 writers: int | None = None,
                 writer_queue: int = DEFAULT_WRITER_QUEUE,
                 meta_interval_s: float = DEFAULT_META_INTERVAL_S,
                 meta_interval_labels: int = DEFAULT_META_INTERVAL_LABELS,
                 mesh="auto",
                 stall_deadline_s: float = 30.0,
                 tenant: str = "-",
                 fs=None,
                 enospc_retry_s: float = 0.5,
                 save_barrier: bool = False):
        self.tenant = tenant
        self.store = LabelStore(data_dir, meta, fs=fs)
        self.enospc_retry_s = enospc_retry_s
        # save_barrier drains the writer pool before every metadata
        # checkpoint: the op stream over the fs layer becomes a pure
        # function of the batch schedule (no writer-thread timing),
        # which is what makes faultfs plans replay-stable — the crash
        # sweep tests and the crash-recovery sim scenario set it; the
        # production default keeps disk/compute overlap through saves
        self.save_barrier = save_barrier
        self.meta = meta
        self.batch = batch_size
        self.progress = progress
        self.inflight = max(int(
            inflight if inflight is not None
            else os.environ.get("SPACEMESH_INFLIGHT", DEFAULT_INFLIGHT)), 1)
        self.writers = max(int(
            writers if writers is not None
            else os.environ.get("SPACEMESH_WRITERS", DEFAULT_WRITERS)), 1)
        self.writer_queue = writer_queue
        self.meta_interval_s = meta_interval_s
        self.meta_interval_labels = meta_interval_labels
        self.stall_deadline_s = stall_deadline_s
        self._fetched = meta.labels_written  # fetch frontier (watchdog)
        self._resume_at = meta.labels_written  # submit-frontier base
        self._mesh_arg = mesh
        self.status = (Status.COMPLETE
                       if meta.labels_written >= meta.total_labels
                       else Status.NOT_STARTED)
        self._stop = False

    def stop(self) -> None:
        """Request stop. The run loop checks this BEFORE dispatching the
        next batch, so stop latency is one fetch+drain, not a full batch
        compute; the durable cursor of already-flushed batches is always
        persisted on the way out."""
        self._stop = True

    # -- mesh routing -------------------------------------------------------

    def _resolve_plan(self, batch_hint: int):
        """-> the mesh this session's batches run on, or None for one
        device: the caller's ``mesh=`` when it gave one, else the one
        rule every caller shares (parallel/mesh.py ``auto_mesh``: every
        visible device on an accelerator, one on the CPU, SPACEMESH_MESH
        forces either way)."""
        if self._mesh_arg is None:
            return None
        if self._mesh_arg != "auto":
            return self._mesh_arg if self._mesh_arg.size > 1 else None
        from ..parallel import mesh as pmesh
        return pmesh.auto_mesh(batch_hint)

    # -- the pipeline -------------------------------------------------------

    def run(self) -> InitResult:
        meta = self.meta
        commitment = bytes.fromhex(meta.commitment)
        total = meta.total_labels
        self.status = Status.IN_PROGRESS
        t0 = time.monotonic()
        written0 = meta.labels_written
        stats = PipelineStats()
        cw = scrypt.commitment_to_words(commitment)

        # the mesh is resolved up front, at the BUCKETED batch — the
        # executable shape every batch of this session (ragged tail
        # included) runs at (ops/scrypt.py shape_bucket) — so the
        # session logs where it will run
        mesh = None
        if total > written0:
            batch_hint = scrypt.shape_bucket(min(self.batch,
                                                 total - written0))
            mesh = self._resolve_plan(batch_hint)
            print(f"init: batch={batch_hint} "
                  f"devices={mesh.size if mesh else 1}",
                  file=sys.stderr, flush=True)
            metrics.post_mesh_devices.set(mesh.size if mesh else 1)

        # resumed (or fresh) running-minimum carry for the VRF scan
        resumed = None
        if meta.vrf_nonce_value is not None and meta.vrf_nonce is not None:
            v = bytes.fromhex(meta.vrf_nonce_value)
            resumed = (int.from_bytes(v[8:], "little"),
                       int.from_bytes(v[:8], "little"))
        carry_host = scrypt.vrf_carry_init(resumed, meta.vrf_nonce or 0)
        if mesh is not None:
            from ..parallel import mesh as pmesh
            carry = pmesh.replicate(mesh, carry_host)
        else:
            carry = jnp.asarray(carry_host)
        # last snapshot whose batch has been retired; valid for saves even
        # while the donated carry buffer keeps rotating on device
        self._snapshot = carry_host

        writer = self.store.start_writer(
            self.writers, self.writer_queue,
            enospc_retry_s=self.enospc_retry_s)
        self._last_save_t = time.monotonic()
        self._last_save_labels = written0
        # liveness (obs/health.py): the fetch frontier and the writer's
        # flushed/durable cursors must both keep advancing while the
        # session runs — a wedged device or disk flips /readyz instead
        # of hanging a silent init forever. post.store is the DEGRADED
        # probe: ENOSPC parks the pool and flips /readyz until space
        # returns (docs/CRASH_SAFETY.md), without killing the session.
        from ..obs import health as health_mod

        # an ENOSPC hold parks the writer pool and backpressure stalls
        # the fetch frontier — that is post.store's DEGRADED verdict,
        # not an init stall (docs/CRASH_SAFETY.md), and it must not
        # read as one: with a restart hook registered below, a
        # stall verdict would STOP a session that PR 13 promised
        # resumes unaided when space returns
        init_wd = health_mod.Watchdog(
            "post.init", progress=lambda: self._fetched,
            deadline_s=self.stall_deadline_s,
            active=lambda: (self.status == Status.IN_PROGRESS
                            and not writer.degraded()))
        writer_wd = health_mod.writer_watchdog(
            writer, deadline_s=self.stall_deadline_s)
        store_probe = health_mod.store_probe(writer)
        health_mod.HEALTH.register("post.init", init_wd.check)
        health_mod.HEALTH.register("post.writer", writer_wd.check)
        health_mod.HEALTH.register("post.store", store_probe)
        # recovery hooks beside the watchdogs (obs/remediate.py): a
        # stalled-init verdict STOPS the session — init is resumable
        # from the durable cursor, so a clean stop hands the restart to
        # the owning supervisor instead of hanging a wedged pipeline
        # forever (docs/SELF_HEALING.md)
        from ..obs import remediate as remediate_mod

        remediate_mod.ACTIONS.register("post.init", "restart_component",
                                       self.stop)
        remediate_mod.ACTIONS.register("post.writer", "restart_component",
                                       self.stop)
        session = tracing.span("init.run",
                               {"total": total, "resume_at": written0,
                                "batch": self.batch,
                                "devices": mesh.size if mesh else 1,
                                "tenant": self.tenant}
                               if tracing.is_enabled() else None)
        session.__enter__()

        # the bounded dispatch->retire window is the shared runtime's
        # (runtime/engine.py); this module supplies only the callbacks.
        # The donated VRF carry is loop-carried state: the dispatch
        # callback rotates it through a one-slot cell.
        carry_cell = [carry]

        def batches():
            dispatched = written0
            while dispatched < total:
                count = min(self.batch, total - dispatched)
                yield dispatched, count
                dispatched += count

        def dispatch(item):
            start, count = item
            words, new_carry, snap = self._dispatch(
                mesh, cw, start, count, carry_cell[0])
            carry_cell[0] = new_carry
            metrics.post_pipeline_dispatched.inc()
            return start, count, words, snap

        def retire(ticket):
            self._retire(ticket, writer, stats)
            self._maybe_save(writer, stats)
            return None

        pipe = engine.Pipeline(
            kind="init", tenant=self.tenant, inflight=self.inflight,
            stop=lambda: self._stop, span="init",
            attrs=lambda item: {"start": item[0], "count": item[1]},
            on_inflight=metrics.post_pipeline_inflight.set)
        try:
            pipe.run(batches(), dispatch, retire)
            if pipe.stats.stopped:
                self.status = Status.STOPPED
            tw = time.perf_counter()
            with tracing.span("init.drain_stall"):
                writer.drain()
            stats.write_stall_s += time.perf_counter() - tw
            self._save_meta(writer, stats)
        finally:
            session.__exit__(None, None, None)
            stats.batches = pipe.stats.batches
            stats.dispatch_s = pipe.stats.dispatch_s
            stats.write_s = writer.write_seconds
            writer.close(drain=False)
            health_mod.HEALTH.unregister("post.init", init_wd.check)
            health_mod.HEALTH.unregister("post.writer", writer_wd.check)
            health_mod.HEALTH.unregister("post.store", store_probe)
            remediate_mod.ACTIONS.unregister(
                "post.init", "restart_component", self.stop)
            remediate_mod.ACTIONS.unregister(
                "post.writer", "restart_component", self.stop)
            # clears the degraded gauge only if THIS session's writer
            # set it — an unconditional zero would clobber another
            # session's live ENOSPC signal (the gauge is process-global)
            writer.clear_degraded()
            metrics.post_pipeline_inflight.set(0)
            metrics.post_pipeline_queue_depth.set(0)

        if meta.labels_written >= total:
            self.status = Status.COMPLETE
        elapsed = time.monotonic() - t0
        done = meta.labels_written - written0
        rate = done / elapsed if elapsed > 0 else 0.0
        metrics.post_pipeline_labels_per_sec.set(rate)
        for stage, secs in (("dispatch", stats.dispatch_s),
                            ("fetch", stats.fetch_s),
                            ("write", stats.write_s),
                            ("stall", stats.write_stall_s)):
            metrics.post_pipeline_stage_seconds.inc(secs, stage=stage)
        return InitResult(
            labels_written=meta.labels_written,
            vrf_nonce=meta.vrf_nonce if meta.vrf_nonce is not None else -1,
            elapsed_s=elapsed,
            labels_per_s=rate,
            stats=stats,
        )

    def _dispatch(self, mesh, cw, start: int, count: int, carry):
        """Enqueue one batch + min-scan on device; returns immediately."""
        n = self.meta.scrypt_n
        if mesh is not None:
            from ..parallel import mesh as pmesh
            # pad to the batch's shape bucket (and at least a multiple of
            # the mesh size) by repeating the last index — duplicates
            # cannot perturb the min scan (same value, first-occurrence
            # index wins) and the pad lanes are trimmed before the bytes
            # reach disk. Bucketing on host here; the sharded wrapper
            # skips its own pad (ops/scrypt.py shape_bucket)
            padded = scrypt.shape_bucket(count)
            if padded % mesh.size:
                padded = count + (-count) % mesh.size
            idx = np.arange(start, start + padded, dtype=np.uint64)
            idx[count:] = start + count - 1
            lo, hi = scrypt.split_indices(idx)
            return pmesh.labels_with_min_sharded(mesh, cw, lo, hi, carry,
                                                 n=n)
        idx = np.arange(start, start + count, dtype=np.uint64)
        lo, hi = scrypt.split_indices(idx)
        return scrypt.scrypt_labels_with_min(
            jnp.asarray(cw), jnp.asarray(lo), jnp.asarray(hi), carry, n=n)

    def _retire(self, item, writer: LabelWriter, stats: PipelineStats) -> None:
        """Fetch the oldest in-flight batch and hand it to the writers."""
        start, count, words, snap = item
        shards = []  # (global start, (4, lanes) ndarray, valid lane count)
        rsp = tracing.span("init.fetch", {"start": start, "count": count}
                           if tracing.is_enabled() else None)
        rsp.__enter__()
        tf = time.perf_counter()
        stall = 0.0
        shard_times: list[tuple[int, float]] = []  # (valid lanes, fetch s)
        try:
            if len(getattr(words.sharding, "device_set", ())) > 1:
                for shard in words.addressable_shards:
                    lane0 = shard.index[1].start or 0
                    if lane0 >= count:
                        continue  # pure padding shard
                    t0 = time.perf_counter()
                    arr = np.asarray(shard.data)
                    valid = min(count - lane0, arr.shape[1])
                    # the FIRST shard's copy blocks until the sharded
                    # program retires, so its time includes compute wait;
                    # later shards are (nearly) pure D2H. Both are what
                    # the operator experiences per shard.
                    shard_times.append((valid, time.perf_counter() - t0))
                    shards.append((start + lane0, arr, valid))
            else:
                shards.append((start, np.asarray(words), count))
            stats.shards += len(shards)
            if len(shard_times) > 1:
                secs = [s for _, s in shard_times]
                hi, lo_ = max(secs), min(secs)
                imbalance = (hi - lo_) / hi if hi > 0 else 0.0
                per_shard = [v / s for v, s in shard_times if s > 0]
                metrics.post_mesh_shard_imbalance.set(imbalance)
                if per_shard:
                    metrics.post_mesh_shard_labels_per_sec.set(
                        sum(per_shard) / len(per_shard))
                rsp.set(shards=len(shard_times),
                        shard_imbalance=round(imbalance, 4))
            for shard_start, arr, valid in shards:
                # byte conversion is host fetch-side work; only the
                # submit() wait is writer backpressure
                data = scrypt.labels_to_bytes(arr)[:valid
                                                   * scrypt.LABEL_BYTES]
                ts = time.perf_counter()
                with tracing.span("init.write_stall"):
                    writer.submit(shard_start, data)
                stall += time.perf_counter() - ts
        finally:
            rsp.__exit__(None, None, None)
        stats.fetch_s += time.perf_counter() - tf - stall
        stats.write_stall_s += stall
        if stall > 0:
            metrics.post_pipeline_stall_seconds.inc(stall)
        metrics.post_pipeline_queue_depth.set(writer.queue_depth())
        metrics.post_pipeline_labels.inc(count)
        self._fetched = start + count
        self._snapshot = snap
        if self.progress:
            self.progress(start + count, self.meta.total_labels)

    # -- metadata durability -------------------------------------------------

    def _maybe_save(self, writer: LabelWriter, stats: PipelineStats) -> None:
        now = time.monotonic()
        # the label trigger fires on the SUBMIT frontier (deterministic
        # per batch schedule), not the flushed cursor (writer-thread
        # timing) — so checkpoint op sequences replay bit-identically
        # under a fault plan
        frontier = self._resume_at + writer.labels_submitted
        if (now - self._last_save_t < self.meta_interval_s
                and frontier - self._last_save_labels
                < self.meta_interval_labels):
            return
        self._save_meta(writer, stats)

    def _save_meta(self, writer: LabelWriter, stats: PipelineStats) -> None:
        """Persist resume metadata. Ordering rule: the cursor is the
        writer's durable (contiguous-FSYNCED) label count — never the
        dispatch or fetch frontier, and never bytes merely handed to
        the OS. ``checkpoint()`` fsyncs the dirty label files first and
        hands back the interval CRC the recovery path verifies on
        reopen (docs/CRASH_SAFETY.md)."""
        meta = self.meta
        t0 = time.perf_counter()
        if self.save_barrier:
            writer.drain()
        # ENOSPC on the checkpoint fsync or the metadata save degrades
        # exactly like an ENOSPC label write: the save path parks (the
        # post.store probe flips, /readyz shows degraded), retries on
        # the writer's interval/kick, and the session survives
        while True:
            try:
                durable, crc = writer.checkpoint()
                break
            except OSError as e:
                if e.errno != errno.ENOSPC or not writer.enospc_wait:
                    raise
                writer.wait_for_space("label-file fsync")
        decoded = scrypt.vrf_carry_decode(self._snapshot)
        meta.labels_written = durable
        prev_end = meta.intervals[-1][0] if meta.intervals else 0
        if durable > prev_end:
            meta.intervals.append([durable, crc])
        if decoded is not None:
            idx, (hi, lo) = decoded
            meta.vrf_nonce = idx
            meta.vrf_nonce_value = (
                lo.to_bytes(8, "little") + hi.to_bytes(8, "little")).hex()
        with tracing.span("init.save_meta", {"durable": durable}
                          if tracing.is_enabled() else None):
            while True:
                try:
                    meta.save(self.store.dir, fs=self.store.fs)
                    break
                except OSError as e:
                    if e.errno != errno.ENOSPC or not writer.enospc_wait:
                        raise
                    writer.wait_for_space("metadata save")
        writer.clear_degraded()
        stats.meta_saves += 1
        stats.save_s += time.perf_counter() - t0
        metrics.post_pipeline_meta_saves.inc()
        self._last_save_t = time.monotonic()
        # record the SAME frontier the trigger compares against: with
        # the durable cursor here, a writer backlog >= the interval
        # would re-trip the label trigger on every retire (a checkpoint
        # storm — fsync + durable metadata write per batch)
        self._last_save_labels = self._resume_at + writer.labels_submitted


def open_or_create_meta(data_dir: Path, *, node_id: bytes,
                        commitment: bytes, num_units: int,
                        labels_per_unit: int, scrypt_n: int = 8192,
                        max_file_size: int = 64 * 1024 * 1024,
                        fs=None) -> PostMetadata:
    """Load (and parameter-check) or create one identity's metadata —
    the create-or-resume gate shared by :func:`initialize` and the
    multi-tenant scheduler's packed init path (runtime/scheduler.py).

    Every reopen runs crash recovery (post/data.py recover_store):
    tail-interval CRC verification, truncation of torn/un-fsynced
    bytes back to the last verified checkpoint, and stray staging-file
    cleanup — so a resumed init always starts from a state the
    durability ledger can vouch for."""
    from .data import recover_store

    dir_ = Path(data_dir)
    if (dir_ / "postdata_metadata.json").exists():
        meta = PostMetadata.load(dir_, fs=fs)
        if (meta.node_id != node_id.hex()
                or meta.commitment != commitment.hex()
                or meta.scrypt_n != scrypt_n
                or meta.labels_per_unit != labels_per_unit
                or meta.num_units != num_units
                or meta.max_file_size != max_file_size):
            raise ValueError(
                "existing POST data directory was initialized with different "
                "parameters; refusing to mix label sets")
        recover_store(dir_, meta, fs=fs)
        return meta
    meta = PostMetadata(
        node_id=node_id.hex(), commitment=commitment.hex(),
        scrypt_n=scrypt_n, num_units=num_units,
        labels_per_unit=labels_per_unit, max_file_size=max_file_size)
    if any(dir_.glob("postdata_*.bin")):
        # a crash before the first metadata save: label bytes with no
        # durable claim behind them — recovery wipes them so the fresh
        # init cannot build on un-fsynced (possibly torn) data
        recover_store(dir_, meta, fs=fs)
    return meta


def initialize(data_dir: str | Path, *, node_id: bytes, commitment: bytes,
               num_units: int, labels_per_unit: int, scrypt_n: int = 8192,
               max_file_size: int = 64 * 1024 * 1024,
               batch_size: int = DEFAULT_BATCH,
               progress: Callable[[int, int], None] | None = None,
               fs=None,
               **pipeline_opts) -> tuple[PostMetadata, InitResult]:
    """Create-or-resume an init session (the `PostSetupManager.StartSession`
    equivalent). Returns final metadata + timing. ``pipeline_opts`` pass
    through to Initializer (inflight, writers, mesh, meta intervals);
    ``fs`` is the injectable I/O layer (post/faultfs.py fault plans)."""
    from ..utils import accel

    accel.enable_persistent_cache()
    dir_ = Path(data_dir)
    meta = open_or_create_meta(
        dir_, node_id=node_id, commitment=commitment, num_units=num_units,
        labels_per_unit=labels_per_unit, scrypt_n=scrypt_n,
        max_file_size=max_file_size, fs=fs)
    init = Initializer(dir_, meta, batch_size=batch_size, progress=progress,
                       fs=fs, **pipeline_opts)
    res = init.run()
    return meta, res
