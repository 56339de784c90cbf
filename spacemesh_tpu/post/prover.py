"""POST proving: k2pow gate + streaming nonce search over the stored labels.

The post-service equivalent (reference's external Rust prover, spawned by
activation/post_supervisor.go:220-298 with --nonces/--threads flags; proof
shape reference common/types/poet.go `Post{Nonce, Indices, Pow}`).

The default path is a streaming pipeline (docs/POST_PROVING.md) mirroring
the init side's (post/initializer.py):

  read      — a bounded background reader pool (post/data.py LabelReader)
              prefetches label batches while the device scans;
  dispatch  — the unit that crosses the host-device boundary is a
              FLIGHT of up to ``FLIGHT_BATCHES`` = 8 consecutive batches
              (131,072 labels at the default batch), up to K flights in
              flight; a flight crosses to the device ONCE: one
              ``jax.device_put`` of its label words and its three
              start/count words (16 B a label: the program makes its own
              lane indices), then one compiled program, the window step
              (``prove_scan_step_window`` /
              ``prove_scan_step_window_pallas``), whose ROLLED loop runs
              the flight's scan steps one ``batch_labels`` wide each:
              the scan kernel once per nonce group of the pass, then
              ONE compaction epilogue over all the groups' nonce rows:
              hits compacted on device and merged into ONE *donated*
              running hit state. A ragged last flight is padded to the
              flight shape and runs only the steps that hold labels, so
              one shape compiles per pass. A store smaller than a flight
              gets the power of two of batches that covers it, so a
              one-batch store runs the one-batch program; on a mesh a
              flight is one batch;
  retire    — the only D2H of a flight is ONE (window_groups *
              nonce_group,) count vector (the flight's per-nonce hits),
              its copy started right after the enqueue
              (``copy_to_host_async``) and read when the flight retires,
              ``inflight - 1`` flights later; the packed (nonce, index)
              hit pairs are fetched once per pass.

One disk pass covers a whole nonce *window* (``window_groups`` groups per
read — on TPU disk bytes are the scarce resource and device FLOPs nearly
free, so the default widens there), and a pass stops early as soon as the
winning nonce is decided: the lowest nonce with >= k2 hits, once every
lower nonce provably cannot reach k2 with the labels left in the pass.
That rule makes the pipelined proof bit-identical to the legacy serial
scan's (kept as ``prove_serial`` — the bench baseline and fallback).

On multi-device the label lanes are sharded over the mesh per batch
(parallel/mesh.py prove_window_step_sharded), the way init shards its
batches, and a flight is one batch.

A proof for challenge ``ch`` is:
    nonce     — the winning proving nonce
    indices   — the first k2 label indices qualifying under nonce
    pow_nonce — k2pow witness for (ch, node_id) (ops/pow.py)
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import pow as k2pow
from ..ops import proving, proving_pallas, scrypt
from ..runtime import engine
from ..utils import accel, metrics, tracing
from .data import LabelStore, PostMetadata

DEFAULT_NONCE_GROUP = 16
DEFAULT_INFLIGHT = 3      # device flights in flight before the oldest retires
DEFAULT_READERS = 2       # background reader threads
DEFAULT_READER_QUEUE = 4  # prefetched flights before reader backpressure
# Batches (scan steps) one host call carries. What the host pays per
# CALL on a v5e's host (PERF.md section 5): device_put ~0.25 ms fixed +
# 0.1 ms of lay-out copy a batch + 2 MiB over PCIe ~0.2, the program
# call 0.32, the async copy, the engine, the retire and the reader's
# get ~0.35: ~2.0 ms a flight of eight = 0.25 ms a scan step, against
# 8 x 0.785 = 6.28 ms of device work when PR 32 chose it (about half
# that since one epilogue serves all 64 rows, PR 35). Four would leave
# 1.7 of 3.1 ms then and no room under the device now; sixteen
# buys nothing more and doubles the overshoot after a decided winner
# (inflight flights) and the reader's buffers (reader_queue flights).
FLIGHT_BATCHES = 8
MAX_GROUPS = 1025         # nonce search gives up past this many groups


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def default_window_groups(platform: str) -> int:
    """Nonce groups per disk pass where the caller names none: on TPU
    disk bytes are the scarce resource and device FLOPs nearly free, so
    the window widens there."""
    return _env_int("SPACEMESH_PROVE_WINDOW_GROUPS",
                    4 if platform == "tpu" else 1)


def bucket_batch(batch_labels: int, use_pallas: bool) -> int:
    """The one compiled batch shape for a requested size: rounded up to
    the compaction segment (the Pallas lane tile on that path), then to
    its power-of-two shape bucket (``Prover.__init__`` says why)."""
    tile = proving_pallas.LANE_TILE if use_pallas else proving.HIT_SEGMENT
    return scrypt.shape_bucket(-(-max(batch_labels, tile) // tile) * tile)


def flight_batches(total_labels: int, batch_labels: int, mesh=None) -> int:
    """Batches a flight of this store carries: ``FLIGHT_BATCHES``, or for
    a store smaller than that the power of two of batches that covers it
    (1, 2, 4: at most four compiled shapes, and nobody scans 131,072
    padded lanes for a 4,096-label store). On a mesh a flight is ONE
    batch: lane-sharding a flight would cut its sub-batches across
    devices, no cell and no chip has run the sharded prover (ROADMAP
    M4), and its flight shape belongs to the PR that brings that cell."""
    if mesh is not None:
        return 1
    batches = -(-total_labels // batch_labels)
    return min(FLIGHT_BATCHES, 1 << (batches - 1).bit_length())


def window_step(nonce_group: int, max_hits: int, batch: int, *,
                use_pallas: bool, mesh=None):
    """The window step of one backend with its static arguments bound
    (``Prover.scan_step`` for a store's prover; the warmers for the
    program a default prover will run): sharded XLA on a mesh, else the
    Pallas step or the XLA step on one device, each a rolled loop of
    ``batch``-lane scan steps over the flight it is handed."""
    if mesh is not None:
        from ..parallel import mesh as pmesh
        return functools.partial(pmesh.prove_window_step_sharded, mesh,
                                 n_nonces=nonce_group, max_hits=max_hits)
    if use_pallas:
        return functools.partial(
            proving_pallas.prove_scan_step_window_pallas,
            n_nonces=nonce_group, max_hits=max_hits, batch=batch,
            interpret=accel.pallas_interpret())
    return functools.partial(proving.prove_scan_step_window,
                             n_nonces=nonce_group, max_hits=max_hits,
                             batch=batch)


@dataclasses.dataclass
class Proof:
    nonce: int
    indices: list[int]          # k2 qualifying label indices, ascending
    pow_nonce: int
    k2: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Proof":
        return cls(**d)


@dataclasses.dataclass
class ProofParams:
    """Difficulty parameters (reference defaults activation/post.go:148,
    mainnet config/mainnet.go:187-189)."""

    k1: int = 26
    k2: int = 37
    k3: int = 37
    pow_difficulty: bytes = bytes([0, 255]) + bytes([255]) * 30


@dataclasses.dataclass
class ProverStats:
    """Per-prove pipeline accounting (tools/profiler.py --prove)."""

    windows: int = 0          # nonce windows swept
    batches: int = 0          # label batches (scan steps) dispatched
    flights: int = 0          # device calls that carried them
    retire_ready: int = 0     # flights whose counts landed before retire
    flights_abandoned: int = 0  # dispatched, dropped by an early exit
    labels_swept: int = 0     # labels covered across all passes
    read_wait_s: float = 0.0  # blocked on the reader pool
    read_io_s: float = 0.0    # filesystem time inside the reader pool
    dispatch_s: float = 0.0   # host time converting + enqueueing batches
    retire_s: float = 0.0     # reading per-batch count vectors
    d2h_bytes: int = 0        # compacted device->host traffic
    early_exited: bool = False
    elapsed_s: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class Prover:
    def __init__(self, data_dir: str | Path, params: ProofParams | None = None,
                 batch_labels: int = 1 << 14,
                 nonce_group: int = DEFAULT_NONCE_GROUP,
                 use_pallas: bool | None = None,
                 pipelined: bool | None = None,
                 window_groups: int | None = None,
                 inflight: int | None = None,
                 readers: int | None = None,
                 reader_queue: int | None = None,
                 mesh="auto",
                 stall_deadline_s: float = 30.0,
                 fs=None):
        # a PostClient builds one per challenge: under a capture this is
        # the proof's first span (metadata, store, the device query)
        with tracing.span("prove.open"):
            # load() raises typed PostMetaCorrupt on a torn/truncated
            # metadata file and clears crash-leftover staging tmps; label
            # reads below get bounded EIO retry (LabelStore._pread_retry),
            # so one transient medium error cannot abort a multi-window
            # disk pass
            self.meta = PostMetadata.load(data_dir, fs=fs)
            if self.meta.labels_written < self.meta.total_labels:
                raise ValueError("POST data is not fully initialized")
            self.store = LabelStore(data_dir, self.meta, fs=fs)
            self.params = params or ProofParams()
            self.nonce_group = nonce_group
            self._platform = jax.devices()[0].platform
            if use_pallas is None:
                # the Pallas scan step (ops/proving_pallas.py) is the
                # default wherever it runs compiled: under Mosaic it
                # matched prove_scan_step_jit bit for bit on a v5e
                # (PERF.md Bring-up); anywhere else it would only
                # interpret
                use_pallas = not accel.pallas_interpret()
            self.use_pallas = use_pallas
            # pipelined batches share one compiled shape: round the batch
            # up to the compaction segment (and the Pallas lane tile on
            # that path), then to its power-of-two shape bucket, so two
            # Provers configured with nearby batch sizes (grpc worker
            # tenants, test fixtures) land on ONE prove_scan_step
            # executable instead of minting one each (ops/scrypt.py
            # shape_bucket; both tiles are powers of two, so bucketing
            # preserves the tile multiple)
            self.batch_labels = bucket_batch(batch_labels, use_pallas)
            if pipelined is None:
                pipelined = os.environ.get(
                    "SPACEMESH_PROVE_PIPELINE", "1") not in ("0", "off")
            self.pipelined = pipelined
            self.window_groups = max(
                window_groups if window_groups is not None
                else default_window_groups(self._platform), 1)
            self.inflight = max(inflight if inflight is not None
                                else _env_int("SPACEMESH_PROVE_INFLIGHT",
                                              DEFAULT_INFLIGHT), 1)
            self.readers = max(readers if readers is not None
                               else _env_int("SPACEMESH_PROVE_READERS",
                                             DEFAULT_READERS), 1)
            self.reader_queue = max(
                reader_queue if reader_queue is not None
                else _env_int("SPACEMESH_PROVE_QUEUE",
                              DEFAULT_READER_QUEUE), 1)
            self._mesh_arg = mesh
            self.stall_deadline_s = stall_deadline_s
            self.last_stats: ProverStats | None = None

    # -- mesh routing (mirrors post/initializer.py) -------------------------

    def _resolve_mesh(self):
        if self._mesh_arg is None:
            return None
        if self._mesh_arg != "auto":
            mesh = self._mesh_arg
            if mesh.size > 1 and self.batch_labels % mesh.size:
                # an explicitly requested mesh must not silently degrade
                # to a single device
                raise ValueError(
                    f"batch_labels {self.batch_labels} not divisible by "
                    f"the {mesh.size}-device mesh; pick a multiple")
            return mesh if mesh.size > 1 else None
        # the rule every caller shares (parallel/mesh.py auto_mesh)
        from ..parallel import mesh as pmesh
        return pmesh.auto_mesh(self.batch_labels)

    # -- entry points -------------------------------------------------------

    def prove(self, challenge: bytes) -> Proof:
        """One whole proof, k2pow gate included. Under a trace capture
        it is one ``prove.proof`` span (docs/OBSERVABILITY.md) that
        says how the proof was reached: ``nonce`` and, on the pipelined
        path, ``passes``, ``labels_swept``, ``early_exited``."""
        with tracing.span("prove.proof",
                          {"challenge": challenge.hex()[:16]}
                          if tracing.is_enabled() else None) as sp:
            if not self.pipelined:
                proof = self.prove_serial(challenge)
                sp.set(nonce=proof.nonce)
                return proof
            session = self.session(challenge)
            try:
                while True:
                    proof = session.step()
                    if proof is not None:
                        st = session.stats
                        sp.set(nonce=proof.nonce, passes=st.windows,
                               labels_swept=st.labels_swept,
                               early_exited=st.early_exited)
                        return proof
            finally:
                session.close()

    def session(self, challenge: bytes, tenant: str = "-") -> "ProveSession":
        """A resumable streaming prove: each ``step()`` is one quantum —
        the k2pow gate first, then one nonce-window disk pass apiece —
        so the multi-tenant scheduler can gang-schedule windows between
        other tenants' work (runtime/scheduler.py). ``prove()`` is just
        a session driven to completion."""
        return ProveSession(self, challenge, tenant=tenant)

    def _prove_pipelined(self, challenge: bytes, pow_nonce: int) -> Proof:
        """Drive a session to completion with the pow gate pre-paid —
        the bench/profiler comparator's entry (post/workload.py), which
        measures the label scan without re-searching the pow per rep."""
        session = self.session(challenge)
        session.pow_nonce = pow_nonce
        try:
            while True:
                proof = session.step()
                if proof is not None:
                    return proof
        finally:
            session.close()

    def prove_serial(self, challenge: bytes) -> Proof:
        """The legacy synchronous scan (read -> scan -> full-mask fetch ->
        host nonzero per group) — kept as the bench baseline and fallback."""
        try:
            return self._prove_serial(challenge, self._pow(challenge))
        finally:
            self.store.close()

    def _pow(self, challenge: bytes) -> int:
        node_id = bytes.fromhex(self.meta.node_id)
        with tracing.span("prove.k2pow"):
            pow_nonce = k2pow.search(challenge, node_id,
                                     self.params.pow_difficulty)
        if pow_nonce is None:
            raise RuntimeError("k2pow search exhausted")
        return pow_nonce

    # -- legacy serial path -------------------------------------------------

    def _prove_serial(self, challenge: bytes, pow_nonce: int) -> Proof:
        meta, p = self.meta, self.params
        t = proving.threshold_u32(p.k1, meta.total_labels)
        cw = jnp.asarray(proving.challenge_words(challenge))
        ng = self.nonce_group
        # Pallas-vs-XLA decided ONCE per prove; ragged tail batches are
        # padded-and-trimmed inside proving_pallas.proving_scan instead of
        # flipping to the XLA path mid-pass (one compiled shape per path)
        use_pallas = self.use_pallas
        group = 0
        while True:
            hits: list[list[int]] = [[] for _ in range(ng)]
            start = 0
            while start < meta.total_labels:
                count = min(self.batch_labels, meta.total_labels - start)
                idx = np.arange(start, start + count, dtype=np.uint64)
                labels = np.frombuffer(
                    self.store.read_labels(start, count), dtype=np.uint8
                ).reshape(count, scrypt.LABEL_BYTES)
                nonce0 = group * ng
                if use_pallas:
                    mask = proving_pallas.proving_scan(
                        challenge, nonce0, idx, labels, t, n_nonces=ng)
                else:
                    lo, hi = scrypt.split_indices(idx)
                    lw = scrypt.labels_to_words(labels)
                    mask = np.asarray(proving.proving_scan_jit(
                        cw, jnp.uint32(nonce0),
                        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(lw),
                        jnp.uint32(t), n_nonces=ng))
                for k in range(ng):
                    if len(hits[k]) < p.k2:
                        found = np.nonzero(mask[k])[0]
                        hits[k].extend((start + found).tolist())
                start += count
            for k in range(ng):
                if len(hits[k]) >= p.k2:
                    metrics.proofs_generated.inc()
                    return Proof(nonce=group * ng + k,
                                 indices=[int(i) for i in hits[k][:p.k2]],
                                 pow_nonce=pow_nonce, k2=p.k2)
            group += 1
            if group > MAX_GROUPS - 1:
                raise RuntimeError("no winning nonce found (k1/k2 mismatch?)")

    # -- streaming pipeline -------------------------------------------------

    def scan_step(self):
        """The window step a pipelined prove runs, bound ONCE per prove:
        ``(step, mesh, impl)`` — the callable, the mesh it shards over
        (None on one device) and which backend it is (``xla-sharded``,
        ``pallas`` or ``xla``). All three take ``(challenge_words, bases,
        label_words, meta, threshold, hit_counts, hit_carry)``, the label
        words and ``meta`` those of one flight (:meth:`flight_batches`
        batches wide), and return ``(hit_counts, batch_counts,
        hit_carry)`` over every nonce of the window (ops/proving.py
        scan_window). ProveSession runs exactly this, and chip_smoke.py
        reports and checks it."""
        mesh = self._resolve_mesh()
        impl = "xla-sharded" if mesh is not None else (
            "pallas" if self.use_pallas else "xla")
        step = window_step(self.nonce_group, max(self.params.k2, 1),
                           self.batch_labels, use_pallas=self.use_pallas,
                           mesh=mesh)
        return step, mesh, impl

    def flight_batches(self, mesh) -> int:
        """Batches one host call carries over this store where its
        batches run on ``mesh`` (module :func:`flight_batches`)."""
        return flight_batches(self.meta.total_labels, self.batch_labels,
                              mesh)

    def _scan_window(self, cw, thr, nonce_base, groups, step, mesh, stats,
                     tenant: str = "-"):
        """One disk pass over the store scanning ``groups`` nonce groups.
        Returns (winner_nonce, indices) or (None, None).

        The bounded read->dispatch->retire window is the shared runtime
        engine's (runtime/engine.py); this method supplies the prove
        callbacks. Its items are FLIGHTS of ``flight_batches`` batches,
        and a flight crosses the host-device boundary once in each
        direction: one upload, one program, one count vector back. Under
        a trace capture the pass is one ``prove.window`` span and every
        per-flight read/dispatch/retire span carries the SAME ``window``
        attribute (the pass's base nonce), so a timeline groups a
        window's whole ladder even when flights from two windows
        interleave."""
        meta, p = self.meta, self.params
        total = meta.total_labels
        b = self.batch_labels
        f = self.flight_batches(mesh) * b
        ng = self.nonce_group
        cap = max(p.k2, 1)
        wsp = tracing.span("prove.window",
                           {"window": nonce_base, "groups": groups,
                            "labels": total}
                           if tracing.is_enabled() else None)
        wsp.__enter__()
        reader = pipe = None
        try:
            with tracing.span("prove.prepare",
                              {"what": "pass", "window": nonce_base}
                              if tracing.is_enabled() else None):
                # flight-sized ranges: one reader.get() is a flight's
                # bytes in one object, and the host concatenates nothing
                ranges = [(s, min(f, total - s))
                          for s in range(0, total, f)]
                # ONE donated hit state for the whole window,
                # group-major; a mesh changes placement and nothing else
                state = list(proving.init_hit_state(groups * ng, cap))
                where = None
                if mesh is not None:
                    from ..parallel import mesh as pmesh
                    state = [pmesh.replicate(mesh, x) for x in state]
                    where = pmesh.prove_batch_shardings(mesh)
                host_counts = np.zeros(ng * groups, dtype=np.int64)
                # the groups' base nonces go up once a pass; a flight's
                # count and start travel with its labels (a device
                # scalar made here would be a host->device transfer of
                # its own: 0.5 ms each on a v5e's host, PERF.md
                # section 6)
                bases = jnp.asarray(
                    nonce_base + ng * np.arange(groups), dtype=jnp.uint32)
                reader = self.store.start_reader(ranges, self.readers,
                                                 self.reader_queue)
            metrics.post_prove_windows.inc()
            stats.windows += 1
            retired_end = [0]

            def dispatch(item):
                start, count = item
                # asked per flight: a capture may start in mid-pass
                traced = tracing.is_enabled()
                tr = time.perf_counter()
                with tracing.span("prove.read_wait",
                                  {"window": nonce_base, "start": start}
                                  if traced else None):
                    raw = reader.get()
                stats.read_wait_s += time.perf_counter() - tr
                with tracing.span("prove.convert", {"window": nonce_base}
                                  if traced else None):
                    # a view of the bytes as read, one row of four LE
                    # words a label; its transpose is the program's
                    # word-major (4, F) and is laid out by the upload
                    words = np.frombuffer(raw, dtype="<u4").reshape(count, 4)
                    if count < f:  # the pass's last flight: one shape
                        words = np.concatenate([
                            words, np.zeros((f - count, 4), words.dtype)])
                    host = [words.T.astype(np.uint32, copy=False),
                            np.array([count, start & 0xFFFFFFFF,
                                      start >> 32], dtype=np.uint32)]
                h2d = sum(a.nbytes for a in host)
                with tracing.span("prove.upload",
                                  {"window": nonce_base, "h2d_bytes": h2d,
                                   "arrays": len(host)}
                                  if traced else None):
                    lw, words = jax.device_put(host, where)
                metrics.post_prove_h2d_bytes.inc(h2d)
                # the scan steps this program runs: the sub-batches of
                # the flight that hold labels
                steps = -(-count // b)
                # the flight's device.flight runs from here to the read
                # of its count vector (_retire)
                t_flight = time.perf_counter_ns() if traced else 0
                with tracing.span("prove.enqueue",
                                  {"window": nonce_base, "groups": groups,
                                   "batch": steps * b, "batches": steps,
                                   "nonces": groups * ng, "programs": 1,
                                   # one compaction epilogue a scan step,
                                   # over all the groups' rows
                                   "epilogues": steps}
                                  if traced else None):
                    state[0], bc, state[1] = step(cw, bases, lw, words, thr,
                                                  *state)
                    # the copy starts now; the flight retires
                    # inflight - 1 flights from here
                    bc.copy_to_host_async()
                # progress must advance PER FLIGHT, here in the callback
                # — folding the engine's count in after the pass would
                # freeze the liveness watchdog for the whole disk pass
                # (ProveSession registers it on stats.batches, which
                # keeps counting batch_labels-wide scan steps)
                stats.batches += steps
                stats.flights += 1
                metrics.post_prove_batches.inc(steps)
                metrics.post_prove_flights.inc()
                return start + count, bc, count, t_flight

            def retire(ticket):
                retired_end[0] = ticket[0]
                if self._retire(ticket, host_counts, total, stats,
                                nonce_base):
                    return ticket[0]  # sound early exit: scanned_end
                return None

            pipe = engine.Pipeline(
                kind="prove", tenant=tenant, inflight=self.inflight,
                span="prove",
                attrs=lambda it: {"window": nonce_base, "start": it[0],
                                  "count": it[1]},
                retire_attrs=lambda tk: {"window": nonce_base,
                                         "end": tk[0], "count": tk[2]})
            rw0 = stats.read_wait_s
            res = pipe.run(ranges, dispatch, retire)
            exited = res is not None
            # the engine's dispatch stage wraps the whole callback; keep
            # the historical read-wait vs dispatch split in the stats
            stats.dispatch_s += max(
                pipe.stats.dispatch_s - (stats.read_wait_s - rw0), 0.0)
            scanned = retired_end[0] if exited else total
        finally:
            with tracing.span("prove.drain", {"window": nonce_base}
                              if tracing.is_enabled() else None):
                if reader is not None:
                    reader.close()
                    stats.read_io_s += reader.read_seconds
            if pipe is not None:
                # flights an early exit dropped: the device still runs
                # them, and the decode below waits for the last one
                abandoned = pipe.stats.abandoned
                stats.flights_abandoned += abandoned
                metrics.post_prove_flights_abandoned.inc(abandoned)
                wsp.set(flights=pipe.stats.batches, abandoned=abandoned)
            wsp.__exit__(None, None, None)
        if exited:
            metrics.post_prove_early_exits.inc()
            stats.early_exited = True
        stats.labels_swept += scanned
        qualified = np.nonzero(host_counts >= p.k2)[0]
        if qualified.size == 0:
            return None, None
        w = int(qualified[0])
        counts, carry = state
        d2h = carry.nbytes + counts.nbytes
        with tracing.span("prove.decode",
                          {"window": nonce_base, "d2h_bytes": d2h}
                          if tracing.is_enabled() else None):
            # its two fetches block until the LAST enqueued flight has
            # run, abandoned ones included
            indices = proving.decode_hits(counts, carry, w, p.k2)
        stats.d2h_bytes += d2h
        metrics.post_prove_d2h_bytes.inc(d2h)
        return nonce_base + w, indices

    def _retire(self, item, host_counts, total, stats,
                nonce_base: int = 0) -> bool:
        """Read one flight's count vector (one per-nonce count for every
        nonce of the window, summed over the flight's batches); True on
        sound early exit: some nonce has k2
        hits and every lower nonce in the window provably cannot reach k2
        with the labels left in this pass (lower windows already failed
        their full pass, so the winner is final and identical to the
        serial prover's end-of-pass pick)."""
        scanned_end, bc, count, t_flight = item
        p = self.params
        tr = time.perf_counter()
        # the engine's prove.retire span (runtime/engine.py) is open here
        # ready: the flight's program had ended and its counts landed
        # before anyone asked; false where the device sets the pace
        ready = bc.is_ready()
        vec = np.asarray(bc)    # the flight's one blocking sync
        host_counts += vec
        stats.retire_ready += ready
        stats.d2h_bytes += vec.nbytes
        metrics.post_prove_d2h_bytes.inc(vec.nbytes)
        if t_flight:
            tracing.interval("device.flight", t_flight,
                             {"program": "prove_scan", "labels": count,
                              "groups": len(vec) // self.nonce_group,
                              "d2h_bytes": vec.nbytes, "syncs": 1,
                              "ready": ready, "window": nonce_base})
        stats.retire_s += time.perf_counter() - tr
        qualified = host_counts >= p.k2
        if not qualified.any():
            return False
        w = int(np.argmax(qualified))
        remaining = total - scanned_end
        exit_now = bool(np.all(host_counts[:w] + remaining < p.k2))
        if exit_now:
            # the decision point the pipelined prover's speedup hinges
            # on: mark it so a timeline shows WHERE the pass stopped
            tracing.instant("prove.early_exit",
                            {"window": nonce_base, "nonce": nonce_base + w,
                             "scanned": scanned_end}
                            if tracing.is_enabled() else None)
        return exit_now


class ProveSession:
    """One resumable streaming prove over an initialized store.

    ``step()`` runs exactly one quantum — the k2pow gate on the first
    call, then one nonce-window disk pass per call — and returns the
    Proof once decided (None until then).  The multi-tenant scheduler
    gang-schedules these quanta between tenants (runtime/scheduler.py);
    ``Prover.prove`` drives a session to completion inline.  ``close()``
    is idempotent and must run on every path: it unregisters the
    liveness watchdog, finalizes the stats/metrics, and drops the
    store's cached read fds (the PR 3 fd-leak class).
    """

    def __init__(self, prover: Prover, challenge: bytes, tenant: str = "-"):
        self.prover = prover
        self.challenge = challenge
        self.tenant = tenant
        self.stats = ProverStats()
        prover.last_stats = self.stats
        self.pow_nonce: int | None = None
        self.proof: Proof | None = None
        self._t0 = time.monotonic()
        self._base = 0
        self._max_nonce = MAX_GROUPS * prover.nonce_group
        self._prep = None
        self._closed = False
        self._scanning = False
        self._span = tracing.span(
            "prove.run",
            {"challenge": challenge.hex()[:16],
             "labels": prover.meta.total_labels, "tenant": tenant}
            if tracing.is_enabled() else None)
        self._span.__enter__()  # spacecheck: ok=SC004 session-lifecycle span; ProveSession.close() exits it on every path (prove()'s finally, the scheduler's abort hook)
        # liveness (obs/health.py): while the session runs, progress must
        # advance PER BATCH, not per window — a healthy disk pass can
        # legitimately outlive the deadline (the window histogram buckets
        # reach 600s), so a per-window counter would false-stall every
        # realistic prove
        from ..obs import health as health_mod

        # active only WHILE a window scan runs (the historical scope:
        # the old code registered after the k2pow gate) — a session
        # parked between scheduler quanta, or one searching pow, has no
        # batch counter to advance and must not read as stalled
        self._wd = health_mod.Watchdog(
            "post.prove",
            progress=lambda: (self.stats.batches, self.stats.labels_swept),
            deadline_s=prover.stall_deadline_s,
            active=lambda: self._scanning)
        health_mod.HEALTH.register("post.prove", self._wd.check)

    @property
    def done(self) -> bool:
        return self.proof is not None

    def step(self) -> Proof | None:
        if self._closed:
            raise RuntimeError("prove session is closed")
        if self.proof is not None:
            return self.proof
        p = self.prover
        if self.pow_nonce is None:
            self.pow_nonce = p._pow(self.challenge)
            return None
        if self._prep is None:
            with tracing.span("prove.prepare", {"what": "session"}
                              if tracing.is_enabled() else None):
                thr = jnp.uint32(proving.threshold_u32(
                    p.params.k1, p.meta.total_labels))
                cw = jnp.asarray(proving.challenge_words(self.challenge))
                stepfn, mesh, _ = p.scan_step()
                self._prep = (cw, thr, mesh, stepfn)
        cw, thr, mesh, stepfn = self._prep
        if self._base >= self._max_nonce:
            raise RuntimeError("no winning nonce found (k1/k2 mismatch?)")
        # clamp the last window to the serial prover's give-up bound so
        # the two paths search the exact same nonce range
        groups = min(p.window_groups,
                     (self._max_nonce - self._base) // p.nonce_group)
        tw = time.perf_counter()
        self._scanning = True
        try:
            winner, indices = p._scan_window(cw, thr, self._base, groups,
                                             stepfn, mesh, self.stats,
                                             tenant=self.tenant)
        finally:
            self._scanning = False
        metrics.post_prove_window_seconds.observe(time.perf_counter() - tw)
        self._base += groups * p.nonce_group
        if winner is None:
            return None
        metrics.proofs_generated.inc()
        self.proof = Proof(nonce=winner, indices=indices,
                           pow_nonce=self.pow_nonce, k2=p.params.k2)
        return self.proof

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        from ..obs import health as health_mod

        health_mod.HEALTH.unregister("post.prove", self._wd.check)
        self._span.__exit__(None, None, None)
        # after the session's span: prove.close nests in what encloses
        # the session (prove.proof) instead of straddling prove.run's end
        with tracing.span("prove.close"):
            stats = self.stats
            stats.elapsed_s = time.monotonic() - self._t0
            if stats.elapsed_s > 0:
                metrics.post_prove_labels_per_sec.set(
                    stats.labels_swept / stats.elapsed_s)
            for stage, secs in (("read", stats.read_wait_s),
                                ("dispatch", stats.dispatch_s),
                                ("retire", stats.retire_s)):
                metrics.post_prove_stage_seconds.inc(secs, stage=stage)
            self.prover.store.close()
