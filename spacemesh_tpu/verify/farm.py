"""Dynamic micro-batching verification farm.

The reference node verifies every incoming ATX/ballot/proposal serially
at ingest (reference activation/handler.go, proposals/handler.go: one
item per gossip callback). That shape wastes exactly the throughput a
batched backend earns: post/verifier.py verifies MANY proofs in one
device pass, and ed25519/ECVRF checks amortize across a worker pool —
but only when someone coalesces the work.

This module is that someone: the continuous-batching pattern from
inference serving applied to crypto verification.

* Callers submit one :class:`VerifyRequest` (ed25519 signature, VRF
  proof, POST proof, poet membership, k2pow witness) on a priority lane
  and await a future with the boolean verdict.
* A per-kind scheduler coalesces pending requests and dispatches a
  batch when it reaches ``max_batch``, when the oldest request's
  lane-latency deadline (2-10 ms) expires, or immediately when the
  backend is idle — so a lone request never waits out the coalescing
  window (the window only pays off under load, which is also the only
  time it fills).
* One POST batch is on the device at a time (DEVICE_INFLIGHT): what
  arrives behind a flight gathers in the lanes and leaves as one wider
  program when it returns, a power of two of items at a time. Host
  kinds overlap up to ``max_inflight``.
* Three lanes — BLOCK (block-critical: certificates, hare-adjacent) >
  GOSSIP > SYNC (backfill) — with per-lane queue bounds. A saturated
  sync lane backpressures its *submitters*; batch composition always
  drains higher-priority lanes first, and a pending BLOCK request
  bypasses the in-flight dispatch cap, so sync floods cannot delay
  block-critical dispatch beyond its deadline.
* Identical in-flight requests deduplicate onto one future (gossip
  storms re-deliver the same ATX from many peers).

Verdicts are decision-identical to the inline verifiers: the farm calls
the same ``EdVerifier.verify`` / ``VrfVerifier.verify`` /
``post_verifier.verify_many`` / ``verify_membership`` code, only
batched. Embedders without an event loop (unit tests, CLI tools) simply
pass ``farm=None`` to the handlers and keep the synchronous path — the
sync-fallback contract (docs/VERIFY_FARM.md).
"""

from __future__ import annotations

import asyncio
import dataclasses
import enum
import os
import time

from typing import Optional

from ..core.signing import EdVerifier, VrfVerifier
from ..post import verifier as post_verifier
from ..post.prover import ProofParams
from ..runtime.queue import KindLanes, LaneGroup, QueueClosed
from ..utils import metrics, sanitize, tracing


class FarmClosed(QueueClosed):
    """The farm was shut down while (or before) the request was pending."""


class Lane(enum.IntEnum):
    """Priority lanes, drained in ascending order."""

    BLOCK = 0   # block-critical: certificates, consensus-blocking checks
    GOSSIP = 1  # live gossip ingest
    SYNC = 2    # backfill / historical sync


KIND_SIG = "sig"
KIND_VRF = "vrf"
KIND_POST = "post"
KIND_MEMBERSHIP = "membership"
KIND_POW = "pow"
KINDS = (KIND_SIG, KIND_VRF, KIND_POST, KIND_MEMBERSHIP, KIND_POW)
# kinds whose backend is ONE program on the device's serial queue
# (_verify_posts -> verify_many -> one device.flight): a second batch
# in flight only queues behind the first, where nothing can merge it.
# What arrives behind a flight stays in the lanes and leaves when the
# flight returns, as one wider program: a label program's time grows
# far slower than its lanes (PERF.md section 5 has the widths). Host
# kinds run side by side on threads and keep max_inflight.
DEVICE_INFLIGHT = {KIND_POST: 1}


@dataclasses.dataclass(frozen=True)
class SigRequest:
    """ed25519 signature check (EdVerifier semantics, domain-separated)."""

    domain: int
    public_key: bytes
    msg: bytes
    signature: bytes

    kind = KIND_SIG

    def key(self) -> tuple:
        return (KIND_SIG, self.domain, self.public_key, self.msg,
                self.signature)


@dataclasses.dataclass(frozen=True)
class VrfRequest:
    """ECVRF proof check (VrfVerifier semantics)."""

    public_key: bytes
    alpha: bytes
    proof: bytes

    kind = KIND_VRF

    def key(self) -> tuple:
        return (KIND_VRF, self.public_key, self.alpha, self.proof)


@dataclasses.dataclass(frozen=True)
class MembershipRequest:
    """PoET merkle-membership check (consensus.poet.verify_membership)."""

    member: bytes
    proof: object  # core.types.MerkleProof
    root: bytes
    leaf_count: int

    kind = KIND_MEMBERSHIP

    def key(self) -> tuple:
        return (KIND_MEMBERSHIP, self.member, self.root, self.leaf_count,
                self.proof.leaf_index, tuple(self.proof.nodes))


@dataclasses.dataclass(frozen=True)
class PostRequest:
    """POST proof check (post.verifier.VerifyItem)."""

    item: post_verifier.VerifyItem

    kind = KIND_POST

    def key(self) -> tuple:
        it = self.item
        return (KIND_POST, it.challenge, it.node_id, it.commitment,
                it.scrypt_n, it.total_labels, it.proof.nonce,
                it.proof.pow_nonce, tuple(it.proof.indices))


@dataclasses.dataclass(frozen=True)
class PowRequest:
    """k2pow witness check (ops/pow.py verify semantics): the
    verification half of the proof-gating proof-of-work, batched across
    items with per-item prefixes and difficulties (verifyd routes remote
    nodes' witness checks here)."""

    challenge: bytes
    node_id: bytes
    difficulty: bytes
    nonce: int

    kind = KIND_POW

    def key(self) -> tuple:
        return (KIND_POW, self.challenge, self.node_id, self.difficulty,
                self.nonce)


class _Pending:
    __slots__ = ("req", "lane", "future", "enqueued", "deadline", "span")

    def __init__(self, req, lane: Lane, future: asyncio.Future,
                 enqueued: float, deadline: float):
        self.req = req
        self.lane = lane
        self.future = future
        self.enqueued = enqueued
        self.deadline = deadline
        self.span = tracing._NOP  # the submitter's request span


class _KindState:
    """Per-kind scheduler state: the runtime's per-lane deques
    (runtime/queue.py KindLanes) + arrival signal + in-flight tasks."""

    def __init__(self, group: LaneGroup) -> None:
        self.lanes = KindLanes(group)
        self.arrived = asyncio.Event()
        self.inflight: set[asyncio.Task] = set()
        self.worker: Optional[asyncio.Task] = None


# default coalescing windows per lane (the ISSUE's 2-10 ms band): block
# work dispatches almost immediately, backfill may wait longest for a
# fuller batch
DEFAULT_MAX_WAIT_S = {Lane.BLOCK: 0.002, Lane.GOSSIP: 0.005,
                      Lane.SYNC: 0.010}
DEFAULT_LANE_BOUNDS = {Lane.BLOCK: 4096, Lane.GOSSIP: 8192,
                       Lane.SYNC: 16384}


class VerificationFarm:
    """Micro-batching admission service for verification work.

    One farm per node (node/app.py). ``submit`` may only be called from
    a running event loop; workers start lazily on first submit and
    rebind automatically if the embedder runs multiple event loops over
    the farm's lifetime (tests that asyncio.run() twice).
    """

    def __init__(self, *, ed_verifier: EdVerifier | None = None,
                 vrf_verifier: VrfVerifier | None = None,
                 post_params: ProofParams | None = None,
                 post_seed: bytes | None = None,
                 max_batch: int = 256,
                 max_inflight: int = 4,
                 max_wait_s: dict[Lane, float] | None = None,
                 lane_bounds: dict[Lane, int] | None = None,
                 sig_threads: int | None = None,
                 stall_deadline_s: float = 30.0,
                 tuner=None):
        self.ed_verifier = ed_verifier or EdVerifier()
        self.vrf_verifier = vrf_verifier or VrfVerifier()
        self.post_params = post_params or ProofParams()
        # deterministic K3 seed for reproducible verification (tests,
        # benches); None = fresh random seed per dispatch, exactly like
        # the inline verify_many default
        self.post_seed = post_seed
        self.max_batch = max(int(max_batch), 1)
        self.max_inflight = max(int(max_inflight), 1)
        self.max_wait_s = dict(DEFAULT_MAX_WAIT_S)
        if max_wait_s:
            self.max_wait_s.update(max_wait_s)
        self.lane_bounds = dict(DEFAULT_LANE_BOUNDS)
        if lane_bounds:
            self.lane_bounds.update(lane_bounds)
        self._sig_threads = sig_threads
        # optional speculative batch-sizing policy (verifyd/batchtune.py
        # BatchTuner, or anything with note_arrival/observe/target_batch/
        # dispatch_now): sizes batches from MEASURED per-kind device
        # rates and dispatches a partially-full batch as soon as the
        # marginal wait for more items exceeds the predicted throughput
        # gain. None keeps the static max_batch + deadline policy.
        self._tuner = tuner
        self._pool = None  # lazy ThreadPoolExecutor for sig/vrf fan-out
        self._loop: asyncio.AbstractEventLoop | None = None
        self.stats = {
            "requests": 0, "dedup_hits": 0, "batches": 0, "items": 0,
            "max_occupancy": 0, "dispatch_s": 0.0, "rejected": 0,
            "queue_peak": {lane.name.lower(): 0 for lane in Lane},
        }
        # stats are mutated on the LOOP only (backend threads return
        # results; the loop-side finally block does the accounting) —
        # owner-write is the runtime twin of that loop-only contract
        self._shared_stats = sanitize.SharedField("verify.farm.stats",
                                                  mode="owner-write")
        # lane accounting (bounds, backpressure waiters with the slot
        # handoff, dedup) is the shared runtime's (runtime/queue.py);
        # this farm keeps only the coalescing policy and the backends
        self._group = LaneGroup(Lane, self.lane_bounds,
                                make_exc=lambda: FarmClosed("farm closed"),
                                on_depth=self._on_depth)
        self._kinds: dict[str, _KindState] = {}
        self._closed = False
        # liveness contract (obs/health.py): while ANY lane holds queued
        # requests, the dispatched-item counter must advance within the
        # deadline — a wedged backend thread or a dead worker task shows
        # up on /readyz instead of as silently-hanging submitters
        from ..obs import health as health_mod
        from ..obs import remediate as remediate_mod

        self._watchdog = health_mod.Watchdog(
            "verify.farm",
            progress=lambda: self.stats["items"],
            active=lambda: self._group.total() > 0,
            deadline_s=stall_deadline_s)
        health_mod.HEALTH.register("verify.farm", self._watchdog.check)
        # per-kind backend breakers (obs/remediate.py): a device backend
        # that keeps raising stops being re-paid per batch — its batches
        # fail FAST with a typed BreakerOpen until a half-open probe
        # batch finds it recovered. Sized generously: only a sustained
        # failure run trips (a lone flaky batch never opens it).
        self._breakers: dict[str, remediate_mod.CircuitBreaker] = {}
        self._breaker_cfg = {"failure_budget": 5, "window_s": 30.0,
                             "cooldown_s": 5.0, "cooldown_cap_s": 60.0}
        # the farm's recovery hook: a stalled-farm verdict resets lanes
        # (fails wedged waiters typed, restarts workers) instead of
        # waiting for an operator (docs/SELF_HEALING.md)
        remediate_mod.ACTIONS.register("verify.farm", "reset_farm_lanes",
                                       self.reset_lanes)

    def _on_depth(self, lane: Lane, depth: int) -> None:
        lname = lane.name.lower()
        metrics.verify_farm_queue_depth.set(depth, lane=lname)
        if depth > self.stats["queue_peak"][lname]:
            self.stats["queue_peak"][lname] = depth

    # --- lifecycle ----------------------------------------------------

    def _bind(self) -> None:
        """Bind scheduler state to the CURRENT running loop; a farm that
        outlives an asyncio.run() rebinds on the next submit (pending
        work from the dead loop is unrecoverable and dropped)."""
        loop = asyncio.get_running_loop()
        if not self._group.bind(loop):
            return
        self._loop = loop
        self._kinds = {kind: _KindState(self._group) for kind in KINDS}

    def _ensure_worker(self, kind: str) -> None:
        st = self._kinds[kind]
        if st.worker is None or st.worker.done():
            st.worker = self._loop.create_task(self._worker(kind))

    def _fail_pending(self) -> None:
        """Fail every queued request and backpressure waiter with
        FarmClosed (the bound loop must still be alive)."""
        for st in self._kinds.values():
            st.arrived.set()
            for p in st.lanes.drain_all():
                if not p.future.done():
                    p.future.set_exception(FarmClosed("farm closed"))
        self._group.fail_waiters()

    async def aclose(self) -> None:
        """Stop workers and fail pending requests with FarmClosed."""
        self._closed = True
        self._group.closed = True
        workers = [st.worker for st in self._kinds.values()
                   if st.worker is not None]
        for w in workers:
            w.cancel()
        self._fail_pending()
        await asyncio.gather(*workers, return_exceptions=True)
        inflight = [t for st in self._kinds.values() for t in st.inflight]
        await asyncio.gather(*inflight, return_exceptions=True)
        self.shutdown()

    def shutdown(self) -> None:
        """Synchronous teardown: drop scheduler state and the worker
        pool. Safe to call twice. Normally App.close runs this after
        the loop exits, but error-path teardown can reach it with the
        loop still alive — then pending futures and backpressure
        waiters must fail with FarmClosed, or handler coroutines
        awaiting submit() hang forever (only aclose() would otherwise
        resolve them)."""
        self._closed = True
        self._group.closed = True
        for st in self._kinds.values():
            if st.worker is not None:
                try:
                    st.worker.cancel()
                except RuntimeError:  # task's loop already torn down
                    pass
        if self._loop is not None and not self._loop.is_closed():
            self._fail_pending()
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
        from ..obs import health as health_mod
        from ..obs import remediate as remediate_mod

        health_mod.HEALTH.unregister("verify.farm", self._watchdog.check)
        remediate_mod.ACTIONS.unregister("verify.farm",
                                         "reset_farm_lanes",
                                         self.reset_lanes)
        for br in self._breakers.values():
            remediate_mod.BREAKERS.unregister(br)
        self._breakers.clear()

    def reset_lanes(self) -> None:
        """The remediation engine's ``reset_farm_lanes`` action: fail
        every queued request and backpressure waiter with a typed
        FarmClosed and restart the workers — a wedged lane recovers to
        an empty, serving farm instead of pinning its submitters until
        process restart. Pending verdicts are LOST (their callers see
        the typed error and re-submit); in-flight backend batches
        resolve normally."""
        if self._closed or self._loop is None or self._loop.is_closed():
            return
        reset_exc = FarmClosed("farm lanes reset by remediation")
        for st in self._kinds.values():
            st.arrived.set()
            for p in st.lanes.drain_all():
                # unlike the close path, the farm keeps serving: every
                # drained entry's lane slot must be released or the
                # lanes stay "full" forever
                self._group.release(p.lane)
                if self._group.dedup.get(p.req.key()) is p:
                    del self._group.dedup[p.req.key()]
                if not p.future.done():
                    p.future.set_exception(reset_exc)
            if st.worker is not None and not st.worker.done():
                st.worker.cancel()
                st.worker = None
        self._group.fail_waiters()

    def _breaker(self, kind: str):
        br = self._breakers.get(kind)
        if br is None:
            from ..obs import remediate as remediate_mod

            br = self._breakers[kind] = remediate_mod.BREAKERS.register(
                remediate_mod.CircuitBreaker(
                    f"verify.farm.{kind}",
                    time_source=self._loop.time,
                    **self._breaker_cfg))
        return br

    # --- submission ---------------------------------------------------

    async def submit(self, req, lane: Lane = Lane.GOSSIP, *,
                     trace_req=None) -> bool:
        """Queue one verification and await its verdict. ``trace_req``
        is the caller's request identifier, recorded as ``req`` on the
        ``farm.request`` span (verifyd hands its request's)."""
        if self._closed:
            raise FarmClosed("farm closed")
        self._bind()
        lane = Lane(lane)
        self._shared_stats.touch()
        self.stats["requests"] += 1
        metrics.verify_farm_requests.inc(kind=req.kind,
                                         lane=lane.name.lower())
        if self._tuner is not None:
            # arrival-rate EWMA feeds the speculative dispatch decision
            self._tuner.note_arrival(req.kind, self._loop.time())
        key = req.key()
        ent = self._group.dedup.get(key)
        if ent is not None and not ent.future.done():
            self.stats["dedup_hits"] += 1
            metrics.verify_farm_dedup_hits.inc()
            if lane < ent.lane:
                # a higher-priority caller must not inherit the queued
                # twin's lane position (a block-critical check stuck
                # behind a sync backlog would defeat the lane contract)
                self._promote(ent, lane)
            # the twin's request span owns the lifecycle; this caller's
            # span just records that it coalesced onto it
            async with tracing.span(
                    "farm.request",
                    {"kind": req.kind, "lane": lane.name.lower(),
                     "dedup": True, "twin": ent.span.id,
                     "req": trace_req}
                    if tracing.is_enabled() else None):
                return await self._await(ent.future)
        sp = tracing.span("farm.request",
                          {"kind": req.kind, "lane": lane.name.lower(),
                           "req": trace_req}
                          if tracing.is_enabled() else None)
        with sp:
            # backpressure: a full lane blocks ITS OWN submitters only
            # (the waiter/slot-handoff semantics live in
            # runtime/queue.py LaneGroup.acquire — the ONE copy)
            if self._group.count(lane) >= self.lane_bounds[lane]:
                async with tracing.span("farm.lane_wait",
                                        {"lane": lane.name.lower()}
                                        if tracing.is_enabled() else None):
                    await self._group.acquire(lane)
            now = self._loop.time()
            pend = _Pending(req, lane, self._loop.create_future(), now,
                            now + self.max_wait_s[lane])
            pend.span = sp
            st = self._kinds[req.kind]
            st.lanes.append(pend)
            self._group.dedup[key] = pend
            self._ensure_worker(req.kind)
            st.arrived.set()
            return await self._await(pend.future)

    @staticmethod
    async def _await(fut: asyncio.Future) -> bool:
        # shield: dedup can hand one future to many awaiters — a caller
        # cancelling its own await must not cancel everyone's verdict
        try:
            return await asyncio.shield(fut)
        except asyncio.CancelledError:
            if fut.cancelled():
                raise FarmClosed("farm closed") from None
            raise

    # --- scheduler ----------------------------------------------------

    async def _worker(self, kind: str) -> None:
        st = self._kinds[kind]
        try:
            while not self._closed:
                st.arrived.clear()
                if st.lanes.count() == 0:
                    await st.arrived.wait()
                    continue
                # one loop turn so same-tick submitters (gather bursts)
                # land in this batch
                await asyncio.sleep(0)
                reason, held_s = await self._coalesce(kind, st)
                if self._closed:
                    break
                # take() is NOT capped at the tuned target: the target
                # is the occupancy worth WAITING for, and a deeper
                # backlog dispatching as one batch both amortizes
                # better and feeds the tuner observations above the
                # target — capping at the target would lock a
                # collapsed model in place (it could never measure a
                # fuller batch again)
                n = min(st.lanes.count(), self.max_batch)
                if kind in DEVICE_INFLIGHT and n:
                    # a device batch is padded to a power of two
                    # (_verify_posts): take a whole one and leave the
                    # rest for the next flight, where it merges with
                    # what arrives meanwhile, rather than fill a
                    # quarter of a program with duplicates
                    n = 1 << (n.bit_length() - 1)
                batch = st.lanes.take(n)
                if not batch:
                    continue
                self._on_taken(batch)
                if held_s > 0:
                    metrics.verify_farm_batches_held.inc(kind=kind)
                # why this batch, this size, now (farm.batch attributes)
                why = ({"reason": reason, "inflight": len(st.inflight),
                        "target": self._batch_limit(kind),
                        "left": st.lanes.count(),
                        "held_ms": round(held_s * 1e3, 3)}
                       if tracing.is_enabled() else None)
                task = self._loop.create_task(
                    self._dispatch(kind, batch, why))
                st.inflight.add(task)
                task.add_done_callback(st.inflight.discard)
        except asyncio.CancelledError:
            pass

    def _batch_limit(self, kind: str) -> int:
        """Per-kind batch-size cap: the tuner's measured-rate target when
        one is attached (capped by max_batch — the device/memory bound),
        else max_batch."""
        if self._tuner is not None:
            target = self._tuner.target_batch(kind)
            if target:
                return max(1, min(int(target), self.max_batch))
        return self.max_batch

    def _tuner_go(self, kind: str, st: _KindState, n: int,
                  now: float) -> bool:
        """Speculative early dispatch: the tuner predicts (from measured
        per-kind rates + the arrival EWMA) that waiting for a fuller
        batch costs more than it gains. Never extends the lane deadline
        — it can only dispatch EARLIER than the 2-10 ms window."""
        if self._tuner is None:
            return False
        oldest = min((q[0].enqueued for q in st.lanes.lanes.values()
                      if q), default=now)
        return bool(self._tuner.dispatch_now(kind, n,
                                             max(now - oldest, 0.0)))

    async def _coalesce(self, kind: str,
                        st: _KindState) -> tuple[str | None, float]:
        """Hold the batch open until it is worth dispatching; returns
        the clause that let it go (``full`` | ``idle`` | ``deadline`` |
        ``tuner``, or ``block`` when only a pending BLOCK request got it
        past the in-flight cap; None when there is nothing to take) and
        the seconds the batch stood ready behind the in-flight cap: a
        clause said go and no slot was free.

        Dispatch NOW when: the batch is full (the per-kind tuned target
        when a batch tuner is attached); the backend is idle (a lone
        request must not wait out the coalescing window); the oldest
        pending deadline has passed and an in-flight slot is free; or
        the tuner's speculative model says the marginal wait for more
        items exceeds the predicted throughput gain. The in-flight cap
        (one batch of a device kind, DEVICE_INFLIGHT; max_inflight of
        the others) throttles small-batch churn under load, and for a
        device kind it is what merges: what gathers behind a flight
        goes as one batch when it returns. A pending BLOCK request
        bypasses the cap, so a saturated sync lane can never keep
        block-critical work in the lanes beyond its deadline."""
        held_since = capped_since = None
        while not self._closed:
            n = st.lanes.count()
            if n == 0:
                return None, 0.0
            # the in-flight cap gates EVERY dispatch (a full batch too:
            # spawning the whole backlog at once would flood the worker
            # pool and anything submitted later — block-critical work
            # included — would queue behind sleeping threads). Only a
            # pending BLOCK request bypasses the cap.
            under_cap = len(st.inflight) < DEVICE_INFLIGHT.get(
                kind, self.max_inflight)
            can_go = under_cap or bool(st.lanes.lanes[Lane.BLOCK])
            now = self._loop.time()
            if self._tuner is None:
                # static policy: full batch, idle fast-path, deadline
                go = ("full" if n >= self.max_batch
                      else "idle" if not st.inflight
                      else "deadline"
                      if st.lanes.earliest_deadline() <= now else None)
            else:
                # tuned policy: the idle fast-path routes through the
                # speculative model too — under service load an idle
                # backend must not slice a filling batch into
                # fragments, and with no model yet (or arrivals gone
                # quiet) dispatch_now returns the fast-path answer
                go = ("full" if n >= self._batch_limit(kind)
                      else "deadline"
                      if st.lanes.earliest_deadline() <= now
                      else "tuner" if self._tuner_go(kind, st, n, now)
                      else None)
            if not can_go and capped_since is None:
                capped_since = now
            if go and held_since is None and capped_since is not None:
                # ready since now, or since a deadline that passed
                # while the cap held it and nothing woke the loop
                held_since = max(min(now, st.lanes.earliest_deadline()),
                                 capped_since)
            if can_go and go:
                held_s = 0.0 if held_since is None else now - held_since
                return (go if under_cap else "block"), held_s
            st.arrived.clear()
            arr = self._loop.create_task(st.arrived.wait())
            waits = {arr} | set(st.inflight)
            # dispatch-eligible: sleep at most until the deadline;
            # capped: sleep until a slot frees or something arrives
            timeout = max(st.lanes.earliest_deadline() - self._loop.time(),
                          0.0005) if can_go else None
            await asyncio.wait(waits, timeout=timeout,
                               return_when=asyncio.FIRST_COMPLETED)
            arr.cancel()
        return None, 0.0

    def _promote(self, ent: _Pending, lane: Lane) -> None:
        """Move a still-queued pending entry to a higher-priority lane
        (dedup hit from that lane); no-op once it is in a dispatch."""
        st = self._kinds[ent.req.kind]
        if not st.lanes.remove(ent):
            return  # already taken into a batch
        ent.lane = lane
        ent.deadline = min(ent.deadline,
                           self._loop.time() + self.max_wait_s[lane])
        st.lanes.append(ent)
        st.arrived.set()

    def _on_taken(self, batch: list[_Pending]) -> None:
        now = self._loop.time()
        for p in batch:
            self._group.release(p.lane)
            wait = max(now - p.enqueued, 0.0)
            metrics.verify_farm_queue_wait_seconds.observe(
                wait, kind=p.req.kind)
            p.span.set(queue_wait_ms=round(wait * 1e3, 3))

    async def _dispatch(self, kind: str, batch: list[_Pending],
                        why: dict | None = None) -> None:
        # the batch span is the hub of the capture: its args carry the
        # member request-span ids, and each member span records the
        # batch id back — so in a Perfetto export a request's wall time
        # decomposes into lane wait vs its batch's backend dispatch
        bsp = tracing.span("farm.batch",
                           {"kind": kind, "n": len(batch),
                            "members": [p.span.id for p in batch],
                            **(why or {})}
                           if tracing.is_enabled() else None)
        for p in batch:
            p.span.set(batch=bsp.id)
        t0 = time.perf_counter()
        br = self._breaker(kind)
        try:
            with bsp:
                if not br.allow():
                    # the kind's backend is known-dead: fail the batch
                    # fast with the typed breaker error instead of
                    # re-paying the failing dispatch (a half-open probe
                    # batch goes through once the cooldown elapses)
                    from ..obs.remediate import BreakerOpen

                    raise BreakerOpen(br.component, br.retry_in())
                results = await asyncio.to_thread(
                    self._run_backend, kind, [p.req for p in batch])
        except Exception as exc:  # noqa: BLE001 — fail the batch, not the farm
            from ..obs.remediate import BreakerOpen

            if not isinstance(exc, BreakerOpen):
                br.record_failure()
            for p in batch:
                if not p.future.done():
                    p.future.set_exception(exc)
        else:
            br.record_success()
            for p, ok in zip(batch, results):
                if not p.future.done():
                    p.future.set_result(bool(ok))
                if not bool(ok):
                    self.stats["rejected"] += 1
            if self._tuner is not None:
                # successful batches only refine the tuner's model — a
                # backend that RAISED in milliseconds must not record a
                # phantom items/s rate
                self._tuner.observe(kind, len(batch),
                                    time.perf_counter() - t0)
        finally:
            dt = time.perf_counter() - t0
            for p in batch:
                if self._group.dedup.get(p.req.key()) is p:
                    del self._group.dedup[p.req.key()]
            self._shared_stats.touch()
            self.stats["batches"] += 1
            self.stats["items"] += len(batch)
            if len(batch) > self.stats["max_occupancy"]:
                self.stats["max_occupancy"] = len(batch)
            self.stats["dispatch_s"] += dt
            metrics.verify_farm_batches.inc(kind=kind)
            metrics.verify_farm_batch_occupancy.observe(len(batch))
            metrics.verify_farm_dispatch_seconds.observe(dt, kind=kind)

    # --- backends (run in a worker thread) ----------------------------

    def _run_backend(self, kind: str, reqs: list) -> list[bool]:
        if kind == KIND_SIG:
            from ..core import signing

            if signing._HAVE_CRYPTOGRAPHY:
                # OpenSSL per-item releases the GIL: thread fan-out wins
                return self._fanout(self._verify_sig, reqs)
            # pure-Python fallback: one random-linear-combination batch
            # check (Pippenger MSM) beats N independent ladders
            return self.ed_verifier.verify_many(
                [(r.domain, r.public_key, r.msg, r.signature)
                 for r in reqs])
        if kind == KIND_VRF:
            return self._fanout(self._verify_vrf, reqs)
        if kind == KIND_MEMBERSHIP:
            from ..consensus.poet import verify_membership

            return [verify_membership(r.member, r.proof, r.root,
                                      r.leaf_count) for r in reqs]
        if kind == KIND_POST:
            return self._verify_posts(reqs)
        if kind == KIND_POW:
            from ..ops import pow as k2pow

            return k2pow.verify_many(
                [(r.challenge, r.node_id, r.difficulty, r.nonce)
                 for r in reqs])
        raise ValueError(f"unknown verify kind {kind!r}")

    def _verify_sig(self, r: SigRequest) -> bool:
        return self.ed_verifier.verify(r.domain, r.public_key, r.msg,
                                       r.signature)

    def _verify_vrf(self, r: VrfRequest) -> bool:
        return self.vrf_verifier.verify(r.public_key, r.alpha, r.proof)

    def _fanout(self, fn, reqs: list) -> list[bool]:
        """Chunk a big batch across the worker pool: OpenSSL ed25519 and
        the native ECVRF library both release the GIL, so wide batches
        verify on every core."""
        threads = self._sig_threads
        if threads is None:
            threads = min(8, os.cpu_count() or 1)
        if threads <= 1 or len(reqs) < 2 * threads:
            return [fn(r) for r in reqs]
        if self._pool is None:
            import concurrent.futures

            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=threads,
                thread_name_prefix="verify-farm")
        chunk = (len(reqs) + threads - 1) // threads
        parts = [reqs[i:i + chunk] for i in range(0, len(reqs), chunk)]
        futs = [self._pool.submit(lambda part=part: [fn(r) for r in part])
                for part in parts]
        out: list[bool] = []
        for f in futs:
            out.extend(f.result())
        return out

    def _verify_posts(self, reqs: list[PostRequest]) -> list[bool]:
        items = [r.item for r in reqs]
        n = len(items)
        # pad to a power-of-two item count so the flattened device shapes
        # recur across occupancies (each new shape is an XLA compile);
        # duplicated lanes are free relative to a recompile
        pad = 1 << (n - 1).bit_length()
        if pad > n and pad <= self.max_batch:
            items = items + [items[0]] * (pad - n)
        return post_verifier.verify_many(
            items, self.post_params, seed=self.post_seed)[:n]
