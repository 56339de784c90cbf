"""Verification farm (spacemesh_tpu/verify/): adversarial batches,
lanes, dedup, cancellation, deadline-expiry, backpressure, and the
sync-fallback contract (ISSUE 2).

The core acceptance property: a farm dispatch mixing valid, invalid,
and structurally malformed proofs must resolve EVERY future with
exactly the accept/reject decision the inline verifier gives for that
item — batching is a scheduling change, never a semantic one.
"""

import asyncio
import collections
import dataclasses
import threading
import time

import pytest

from spacemesh_tpu.consensus import malfeasance
from spacemesh_tpu.core import types
from spacemesh_tpu.core.signing import Domain, EdSigner, EdVerifier
from spacemesh_tpu.p2p.pubsub import PubSub
from spacemesh_tpu.post.prover import Proof
from spacemesh_tpu.post.verifier import VerifyItem
from spacemesh_tpu.storage import db as dbmod
from spacemesh_tpu.storage.cache import AtxCache
from spacemesh_tpu.utils import metrics, tracing
from spacemesh_tpu.verify import workload
from spacemesh_tpu.verify.farm import (
    KIND_POST,
    KIND_SIG,
    FarmClosed,
    Lane,
    PostRequest,
    SigRequest,
    VerificationFarm,
)


@pytest.fixture(scope="module")
def wl(tmp_path_factory):
    """One small mixed workload (includes malformed items) per module —
    the POST init + proofs inside are the expensive part."""
    d = tmp_path_factory.mktemp("verify-wl")
    return workload.build(str(d), sigs=20, vrfs=6, posts=10,
                          memberships=8, post_challenges=2)


def _farm_for(wl, **kw):
    kw.setdefault("ed_verifier", wl.ed)
    kw.setdefault("vrf_verifier", wl.vrf)
    kw.setdefault("post_params", wl.post_params)
    kw.setdefault("post_seed", wl.post_seed)
    return VerificationFarm(**kw)


def _sig_reqs(n, valid=True, salt=b""):
    s = EdSigner(seed=bytes(31) + b"\x01")
    out = []
    for i in range(n):
        msg = b"m" + salt + i.to_bytes(4, "little")
        sig = s.sign(Domain.HARE, msg)
        if not valid:
            sig = bytes(64)
        out.append(SigRequest(int(Domain.HARE), s.public_key, msg, sig))
    return out


class _BlockingBackend:
    """Wrap farm._run_backend so the FIRST dispatch blocks on an event
    (simulating a slow device pass) while later dispatches run live."""

    def __init__(self, farm, block_first=1, sleep_s=0.0):
        self.real = farm._run_backend
        self.gate = threading.Event()
        self.block_left = block_first
        self.sleep_s = sleep_s
        self.lock = threading.Lock()
        farm._run_backend = self  # type: ignore[method-assign]

    def __call__(self, kind, reqs):
        with self.lock:
            blocked = self.block_left > 0
            self.block_left -= 1 if blocked else 0
        if blocked:
            assert self.gate.wait(30), "test gate never released"
        if self.sleep_s:
            time.sleep(self.sleep_s)
        return self.real(kind, reqs)


# --- decision parity ------------------------------------------------------


def test_adversarial_batch_matches_inline(wl):
    """Valid + invalid + malformed, all lanes, one farm: bit-identical
    accept/reject decisions vs the inline verifiers."""
    expected = wl.inline_all()
    assert 0 < sum(expected) < len(expected), "workload must be mixed"

    async def main():
        farm = _farm_for(wl)
        lanes = [Lane.BLOCK, Lane.GOSSIP, Lane.SYNC]
        got = await asyncio.gather(
            *(farm.submit(r, lane=lanes[i % 3])
              for i, r in enumerate(wl.requests)))
        await farm.aclose()
        return got

    got = asyncio.run(main())
    assert got == expected


def test_parity_across_repeat_submission(wl):
    """Same workload a second time through one farm (dedup entries from
    resolved batches must not leak stale verdicts)."""

    async def main():
        farm = _farm_for(wl)
        first = await asyncio.gather(*(farm.submit(r)
                                       for r in wl.requests))
        second = await asyncio.gather(*(farm.submit(r)
                                        for r in wl.requests))
        await farm.aclose()
        return first, second

    first, second = asyncio.run(main())
    assert first == second == wl.inline_all()


# --- scheduler behavior ---------------------------------------------------


def test_dedup_shares_one_verdict():
    async def main():
        farm = VerificationFarm()
        bb = _BlockingBackend(farm)
        [req] = _sig_reqs(1)
        t1 = asyncio.ensure_future(farm.submit(req))
        await asyncio.sleep(0.05)  # t1's batch is now blocked in dispatch
        t2 = asyncio.ensure_future(farm.submit(req))
        t3 = asyncio.ensure_future(farm.submit(req))
        await asyncio.sleep(0.05)
        bb.gate.set()
        got = await asyncio.gather(t1, t2, t3)
        stats = dict(farm.stats)
        await farm.aclose()
        return got, stats

    got, stats = asyncio.run(main())
    assert got == [True, True, True]
    assert stats["dedup_hits"] >= 2
    assert stats["items"] == 1  # one request ever reached a backend


def test_dedup_promotes_to_higher_priority_lane():
    """A BLOCK-lane submit that dedups onto a queued SYNC twin must pull
    the entry into the BLOCK lane — not inherit SYNC's queue position."""

    async def main():
        farm = VerificationFarm(max_inflight=1)
        bb = _BlockingBackend(farm)
        first = asyncio.ensure_future(farm.submit(_sig_reqs(1)[0]))
        await asyncio.sleep(0.05)  # dispatch blocked; cap=1 saturated
        [req] = _sig_reqs(1, salt=b"pm")
        sync_t = asyncio.ensure_future(farm.submit(req, lane=Lane.SYNC))
        await asyncio.sleep(0.02)  # queued, held by the in-flight cap
        t0 = time.perf_counter()
        # without promotion this waits on the capped SYNC entry until
        # the gate opens; with it, BLOCK bypasses the cap at its deadline
        ok = await asyncio.wait_for(farm.submit(req, lane=Lane.BLOCK), 5)
        latency = time.perf_counter() - t0
        bb.gate.set()
        assert await sync_t is True  # the shared verdict reached both
        assert await first is True
        await farm.aclose()
        return ok, latency

    ok, latency = asyncio.run(main())
    assert ok is True
    assert latency < 1.0, latency


def test_deadline_dispatches_partial_batch():
    """With the backend busy, queued requests must dispatch when the
    lane's max-latency deadline expires — NOT wait for max_batch."""

    async def main():
        farm = VerificationFarm(max_batch=10_000)
        bb = _BlockingBackend(farm)
        first = asyncio.ensure_future(farm.submit(_sig_reqs(1)[0]))
        await asyncio.sleep(0.05)  # first dispatch now blocked
        reqs = _sig_reqs(5, salt=b"dl")
        t0 = time.perf_counter()
        got = await asyncio.gather(*(farm.submit(r) for r in reqs))
        latency = time.perf_counter() - t0
        stats = dict(farm.stats)
        bb.gate.set()
        assert await first is True
        await farm.aclose()
        return got, latency, stats

    got, latency, stats = asyncio.run(main())
    assert got == [True] * 5
    # 5ms gossip deadline, generous CI margin — the point is "well under
    # forever", since max_batch can never fill
    assert latency < 5.0
    assert stats["max_occupancy"] >= 5  # the 5 coalesced into one batch


def test_block_lane_not_starved_by_sync_flood():
    """Acceptance: a saturated sync lane never delays block-critical
    dispatch beyond its deadline (the BLOCK lane bypasses the in-flight
    cap and is drained first)."""

    async def main():
        farm = VerificationFarm(max_batch=8, max_inflight=2)
        _BlockingBackend(farm, block_first=0, sleep_s=0.15)
        flood = [asyncio.ensure_future(farm.submit(r, lane=Lane.SYNC))
                 for r in _sig_reqs(160, salt=b"fl")]
        await asyncio.sleep(0.05)  # flood is mid-dispatch, lanes deep
        t0 = time.perf_counter()
        ok = await farm.submit(_sig_reqs(1, salt=b"blk")[0],
                               lane=Lane.BLOCK)
        block_latency = time.perf_counter() - t0
        still_pending = sum(1 for f in flood if not f.done())
        await asyncio.gather(*flood)
        await farm.aclose()
        return ok, block_latency, still_pending

    ok, block_latency, still_pending = asyncio.run(main())
    assert ok is True
    # 160 sync items at 0.15s per 8-item batch ≈ seconds of flood; the
    # block item must not ride out the whole flood
    assert block_latency < 1.0, block_latency
    assert still_pending > 16, still_pending  # flood genuinely mid-drain


def test_sync_backpressure_bounds_queue():
    async def main():
        farm = VerificationFarm(lane_bounds={Lane.SYNC: 4})
        bb = _BlockingBackend(farm, block_first=100)
        tasks = [asyncio.ensure_future(farm.submit(r, lane=Lane.SYNC))
                 for r in _sig_reqs(12, salt=b"bp")]
        await asyncio.sleep(0.1)
        peak = farm.stats["queue_peak"]["sync"]
        bb.gate.set()
        bb.block_left = 0
        got = await asyncio.gather(*tasks)
        await farm.aclose()
        return peak, got

    peak, got = asyncio.run(main())
    assert peak <= 4  # submitters beyond the bound BLOCKED, not queued
    assert got == [True] * 12  # and everyone still got a verdict


def test_cancelled_caller_leaves_batch_intact():
    async def main():
        farm = VerificationFarm()
        bb = _BlockingBackend(farm)
        first = asyncio.ensure_future(farm.submit(_sig_reqs(1)[0]))
        await asyncio.sleep(0.05)
        reqs = _sig_reqs(3, salt=b"cx")
        tasks = [asyncio.ensure_future(farm.submit(r)) for r in reqs]
        await asyncio.sleep(0)
        tasks[1].cancel()
        bb.gate.set()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        assert await first is True
        await farm.aclose()
        return results

    r = asyncio.run(main())
    assert r[0] is True and r[2] is True
    assert isinstance(r[1], asyncio.CancelledError)


def test_cancelled_waiter_hands_freed_slot_to_next():
    """A backpressure waiter cancelled AFTER _release_lane resolved it
    (but before its submit resumed) must pass the freed slot on —
    otherwise the grant is lost and surviving waiters can park forever
    once the lane drains with no further releases."""

    async def main():
        farm = VerificationFarm()
        assert await farm.submit(_sig_reqs(1, salt=b"w0")[0]) is True
        lane = Lane.SYNC
        # the lane accounting lives in the shared runtime queue now
        # (runtime/queue.py LaneGroup) — same semantics, one copy
        group = farm._group
        group._count[lane] = farm.lane_bounds[lane]  # lane "full"
        b = asyncio.ensure_future(
            farm.submit(_sig_reqs(1, salt=b"wb")[0], lane=lane))
        c = asyncio.ensure_future(
            farm.submit(_sig_reqs(1, salt=b"wc")[0], lane=lane))
        for _ in range(3):
            await asyncio.sleep(0)
        assert len(group._waiters[lane]) == 2
        group.release(lane)       # frees one slot: resolves b's waiter
        b.cancel()                # ...which b will never consume
        with pytest.raises(asyncio.CancelledError):
            await b
        ok = await asyncio.wait_for(c, 5)  # hangs without the handoff
        await farm.aclose()
        return ok

    assert asyncio.run(main()) is True


def test_sync_shutdown_with_live_loop_fails_pending():
    """App.close() runs the SYNC shutdown(); on error-path teardown the
    loop may still be alive — queued requests and backpressure waiters
    must then fail with FarmClosed instead of hanging forever."""

    async def main():
        farm = VerificationFarm(max_inflight=1,
                                lane_bounds={Lane.SYNC: 1})
        bb = _BlockingBackend(farm)
        inflight = asyncio.ensure_future(farm.submit(_sig_reqs(1)[0]))
        await asyncio.sleep(0.05)  # dispatched and blocked in backend
        queued = asyncio.ensure_future(
            farm.submit(_sig_reqs(1, salt=b"q")[0], lane=Lane.SYNC))
        waiting = asyncio.ensure_future(
            farm.submit(_sig_reqs(1, salt=b"w")[0], lane=Lane.SYNC))
        await asyncio.sleep(0.02)  # queued fills the lane; waiting parks
        farm.shutdown()  # the sync path, loop still running
        with pytest.raises(FarmClosed):
            await asyncio.wait_for(queued, 5)
        with pytest.raises(FarmClosed):
            await asyncio.wait_for(waiting, 5)
        bb.gate.set()  # already-dispatched work still completes
        assert await inflight is True
        await farm.aclose()

    asyncio.run(main())


def test_close_fails_pending_with_farm_closed():
    async def main():
        # max_inflight=1: with the first dispatch blocked, later submits
        # stay QUEUED (the cap holds them) instead of dispatching at the
        # deadline — the state aclose() must fail fast
        farm = VerificationFarm(max_inflight=1)
        bb = _BlockingBackend(farm)
        inflight = asyncio.ensure_future(farm.submit(_sig_reqs(1)[0]))
        await asyncio.sleep(0.05)
        queued = asyncio.ensure_future(
            farm.submit(_sig_reqs(1, salt=b"q")[0]))
        await asyncio.sleep(0.02)
        closer = asyncio.ensure_future(farm.aclose())
        await asyncio.sleep(0.02)
        with pytest.raises(FarmClosed):
            await queued  # queued-but-undispatched work fails fast
        bb.gate.set()  # let the in-flight dispatch finish
        assert await inflight is True  # already-dispatched work completes
        await closer
        with pytest.raises(FarmClosed):
            await farm.submit(_sig_reqs(1, salt=b"z")[0])

    asyncio.run(main())


# --- one POST batch on the device at a time -------------------------------


def _post_reqs(n, salt):
    """Distinct POST requests for a stubbed backend (never verified)."""
    return [PostRequest(VerifyItem(
        proof=Proof(nonce=0, indices=[i], pow_nonce=0, k2=1),
        challenge=salt.ljust(32, b"\0"), node_id=bytes(32),
        commitment=bytes(32), scrypt_n=2, total_labels=64))
        for i in range(n)]


class _GatedBackend:
    """Stand-in for farm._run_backend: records every call in the order
    the backend took it, holds the first ``gate_first[kind]`` calls of a
    kind until ``gate`` is set (a flight on the device), sleeps
    ``sleep_s`` and answers True for every item."""

    def __init__(self, farm, gate_first=None, sleep_s=0.0):
        self.gate = threading.Event()
        self.gate_left = dict(gate_first or {})
        self.sleep_s = sleep_s
        self.lock = threading.Lock()
        self.calls = []
        self.active = collections.Counter()
        self.peak = collections.Counter()
        farm._run_backend = self  # type: ignore[method-assign]

    def __call__(self, kind, reqs):
        with self.lock:
            self.calls.append((kind, list(reqs)))
            self.active[kind] += 1
            self.peak[kind] = max(self.peak[kind], self.active[kind])
            gated = self.gate_left.get(kind, 0) > 0
            if gated:
                self.gate_left[kind] -= 1
        try:
            if gated:
                assert self.gate.wait(30), "test gate never released"
            if self.sleep_s:
                time.sleep(self.sleep_s)
            return [True] * len(reqs)
        finally:
            with self.lock:
                self.active[kind] -= 1

    def of(self, kind):
        with self.lock:
            return [reqs for k, reqs in self.calls if k == kind]


class _FixedTuner:
    """The tuner's four hooks with verifyd's answers in the synced cell:
    a batch is full at ``target`` and anything smaller goes at once."""

    def __init__(self, target):
        self.target = target

    def note_arrival(self, kind, now):
        pass

    def observe(self, kind, batch, seconds):
        pass

    def target_batch(self, kind):
        return self.target

    def dispatch_now(self, kind, n, oldest_age_s):
        return True


def _submit_all(farm, reqs, lane=Lane.GOSSIP):
    return [asyncio.ensure_future(farm.submit(r, lane=lane)) for r in reqs]


@pytest.mark.parametrize("tuner", [None, _FixedTuner(4)],
                         ids=["static", "tuned"])
def test_post_gathers_behind_a_flight_and_leaves_as_one_batch(tuner):
    """With one POST batch on the device, three further groups stay in
    the lanes whatever full / deadline / tuner say, and leave together
    when the flight returns: whole, in lane order, as ONE batch."""

    async def main():
        farm = VerificationFarm(tuner=tuner)
        be = _GatedBackend(farm, gate_first={KIND_POST: 1})
        first = _submit_all(farm, _post_reqs(4, b"g0"))
        await asyncio.sleep(0.05)  # the first batch is on the device
        groups = [(_post_reqs(4, b"g1"), Lane.GOSSIP),
                  (_post_reqs(2, b"g2"), Lane.SYNC),
                  (_post_reqs(2, b"g3"), Lane.GOSSIP)]
        tasks = []
        for reqs, lane in groups:
            tasks += _submit_all(farm, reqs, lane)
            await asyncio.sleep(0.03)  # past every lane's deadline
        assert len(be.of(KIND_POST)) == 1 and not any(
            t.done() for t in tasks)
        be.gate.set()
        got = await asyncio.wait_for(asyncio.gather(*first, *tasks), 10)
        await farm.aclose()
        return got, be, groups

    got, be, groups = asyncio.run(main())
    assert got == [True] * 12
    calls = be.of(KIND_POST)
    assert [len(c) for c in calls] == [4, 8] and be.peak[KIND_POST] == 1
    (g1, _), (g2, _), (g3, _) = groups
    assert calls[1] == g1 + g3 + g2  # GOSSIP drains before SYNC, FIFO


def test_a_gathered_post_batch_is_a_whole_power_of_two():
    """The backend pads a POST batch to a power of two of items, so the
    farm takes a whole one and leaves the rest for the next flight: 11
    gathered go as 8, 2 and 1, in lane order, one at a time. It is what
    makes a closed loop of four settle at two even batches a round."""

    async def main():
        farm = VerificationFarm()
        be = _GatedBackend(farm, gate_first={KIND_POST: 1})
        first = _submit_all(farm, _post_reqs(4, b"w0"))
        await asyncio.sleep(0.03)
        held = _post_reqs(11, b"w1")
        tasks = _submit_all(farm, held, Lane.SYNC)
        await asyncio.sleep(0.03)
        be.gate.set()
        got = await asyncio.wait_for(asyncio.gather(*first, *tasks), 10)
        await farm.aclose()
        return got, be, held

    got, be, held = asyncio.run(main())
    assert got == [True] * 15 and be.peak[KIND_POST] == 1
    calls = be.of(KIND_POST)
    assert [len(c) for c in calls] == [4, 8, 2, 1]
    assert calls[1] + calls[2] + calls[3] == held


def test_sig_batches_still_overlap_beside_a_post_flight():
    """The cap of one is the device kind's: host kinds run side by side
    up to max_inflight while a POST batch is out and another is held."""

    async def main():
        farm = VerificationFarm(max_inflight=3)
        be = _GatedBackend(farm, gate_first={KIND_POST: 9, KIND_SIG: 9})
        tasks = _submit_all(farm, _post_reqs(2, b"p0"))
        await asyncio.sleep(0.03)
        tasks += _submit_all(farm, _post_reqs(2, b"p1"))  # held
        for r in _sig_reqs(5, salt=b"ov"):
            tasks += _submit_all(farm, [r])
            await asyncio.sleep(0.02)  # each its own deadline batch
        active = dict(be.active)
        be.gate.set()
        got = await asyncio.wait_for(asyncio.gather(*tasks), 10)
        await farm.aclose()
        return got, active, be

    got, active, be = asyncio.run(main())
    assert got == [True] * 9
    assert active == {KIND_POST: 1, KIND_SIG: 3}
    assert be.peak[KIND_POST] == 1 and be.peak[KIND_SIG] == 3
    assert [len(c) for c in be.of(KIND_POST)] == [2, 2]


def test_block_lane_post_request_passes_the_device_cap():
    """The lane contract outranks the merge: a pending BLOCK request
    takes the queue with it past a flight that has not returned."""

    async def main():
        farm = VerificationFarm()
        be = _GatedBackend(farm, gate_first={KIND_POST: 1})
        first = _submit_all(farm, _post_reqs(1, b"b0"))
        await asyncio.sleep(0.03)
        held = _post_reqs(3, b"b1")
        held_tasks = _submit_all(farm, held, Lane.SYNC)
        await asyncio.sleep(0.03)
        assert len(be.of(KIND_POST)) == 1
        [blk] = _post_reqs(1, b"b2")
        ok = await asyncio.wait_for(farm.submit(blk, lane=Lane.BLOCK), 5)
        assert not first[0].done()  # the flight ahead is still out
        be.gate.set()
        got = await asyncio.gather(*first, *held_tasks)
        await farm.aclose()
        return ok, got, be.of(KIND_POST), blk, held

    ok, got, calls, blk, held = asyncio.run(main())
    assert ok is True and got == [True] * 4
    assert calls[1] == [blk] + held


@pytest.mark.parametrize("how", ["aclose", "shutdown", "reset_lanes"])
def test_held_post_requests_fail_typed_like_queued_ones(how):
    async def main():
        farm = VerificationFarm()
        be = _GatedBackend(farm, gate_first={KIND_POST: 1})
        inflight = _submit_all(farm, _post_reqs(2, b"c0"))
        await asyncio.sleep(0.03)
        held_reqs = _post_reqs(3, b"c1")
        held = _submit_all(farm, held_reqs, Lane.SYNC)
        await asyncio.sleep(0.03)  # ready (deadline passed), and held
        closer = None
        if how == "aclose":
            closer = asyncio.ensure_future(farm.aclose())
            await asyncio.sleep(0.02)
        else:
            getattr(farm, how)()
        for t in held:
            with pytest.raises(FarmClosed):
                await asyncio.wait_for(t, 5)
        be.gate.set()  # the flight itself still lands
        assert await asyncio.gather(*inflight) == [True, True]
        if closer is not None:
            await closer
        if how == "reset_lanes":  # ...and the farm keeps serving
            assert farm._group.total() == 0
            assert await asyncio.wait_for(
                farm.submit(_post_reqs(1, b"c2")[0]), 5) is True
        await farm.aclose()
        return be.of(KIND_POST), held_reqs

    calls, held_reqs = asyncio.run(main())
    assert [len(c) for c in calls][:1] == [2]
    went = [r for c in calls for r in c]
    assert not any(r in went for r in held_reqs)  # the held never went


@pytest.mark.parametrize("tuner", [None, _FixedTuner(8)],
                         ids=["static", "tuned"])
def test_closed_loop_of_four_settles_at_two_batches_a_round(tuner):
    """Four clients, each sending its next request of 8 proofs when the
    last has answered, over a backend that takes 60 ms whatever the
    width: the first to arrive goes alone, of the three that gather
    behind it two leave together (a power of two) and the third waits
    for the first one's next request, and from then on one pair's
    requests gather while the other's program runs, so a round of four
    requests is two even batches (it was four, one after another on the
    device)."""
    rounds, per_request, clients = 6, 8, 4

    async def main():
        farm = VerificationFarm(tuner=tuner)
        be = _GatedBackend(farm, sleep_s=0.06)

        async def client(c):
            await asyncio.sleep(0.01 * c)  # as over HTTP: never one tick
            for i in range(rounds):
                got = await asyncio.gather(*(
                    farm.submit(r, lane=Lane.SYNC) for r in
                    _post_reqs(per_request, b"cl%d-%d" % (c, i))))
                assert got == [True] * per_request

        t0 = time.perf_counter()
        await asyncio.wait_for(
            asyncio.gather(*(client(c) for c in range(clients))), 30)
        wall = time.perf_counter() - t0
        await farm.aclose()
        return be, wall

    be, wall = asyncio.run(main())
    sizes = [len(c) for c in be.of(KIND_POST)]
    assert sum(sizes) == rounds * clients * per_request
    assert all(n % per_request == 0 for n in sizes)  # whole requests
    assert be.peak[KIND_POST] == 1
    # two a round and the odd one at the start (slack: a stalled loop
    # can split a group once or twice); one batch a request was 24
    assert 2 * rounds <= len(sizes) <= 2 * rounds + 3, sizes
    assert 3 * per_request not in sizes, sizes
    assert sizes.count(2 * per_request) >= len(sizes) - 4, sizes
    assert wall < 0.06 * (2 * rounds + 4) + 1.0, wall


def test_held_ms_and_the_held_counter_read_what_happened():
    def held_count(kind):
        return metrics.verify_farm_batches_held.sample().get(
            (("kind", kind),), 0.0)

    async def main():
        farm = VerificationFarm()
        be = _GatedBackend(farm, gate_first={KIND_POST: 1})
        tasks = _submit_all(farm, _post_reqs(2, b"h0"))
        await asyncio.sleep(0.03)
        tasks += _submit_all(farm, _post_reqs(4, b"h1"))
        tasks += _submit_all(farm, _sig_reqs(2, salt=b"h"))
        await asyncio.sleep(0.08)  # ready after 5 ms, held ~75 more
        be.gate.set()
        await asyncio.wait_for(asyncio.gather(*tasks), 10)
        [blk] = _post_reqs(1, b"h2")  # nothing in flight: goes at once
        await farm.submit(blk, lane=Lane.BLOCK)
        await farm.aclose()

    before = {k: held_count(k) for k in (KIND_POST, KIND_SIG)}
    tracing.stop()
    tracing.start(capacity=1 << 12, jax_bridge=False)
    try:
        asyncio.run(main())
    finally:
        tracing.stop()
    assert held_count(KIND_POST) == before[KIND_POST] + 1
    assert held_count(KIND_SIG) == before[KIND_SIG]
    batches = sorted((e for e in tracing.export()["traceEvents"]
                      if e["ph"] == "X" and e["name"] == "farm.batch"),
                     key=lambda e: e["ts"])
    post = [b["args"] for b in batches if b["args"]["kind"] == KIND_POST]
    assert [a["n"] for a in post] == [2, 4, 1]
    assert [a["inflight"] for a in post] == [0, 0, 0]
    assert post[0]["held_ms"] == 0 and post[2]["held_ms"] == 0
    assert 40 <= post[1]["held_ms"] <= 5000, post[1]
    assert post[1]["reason"] == "idle"  # the clause when it was let go
    (sig,) = [b["args"] for b in batches if b["args"]["kind"] == KIND_SIG]
    assert sig["held_ms"] == 0 and sig["n"] == 2


# --- handler integration: farm path == inline path ------------------------


def _signed_ballot(signer, layer, salt=0):
    b = types.Ballot(
        layer=layer, atx_id=bytes([salt]) * 32, epoch_data=None,
        ref_ballot=bytes(32), eligibilities=[],
        opinion=types.Opinion(base=bytes(32), support=[], against=[],
                              abstain=[]),
        node_id=signer.node_id, signature=bytes(64))
    return dataclasses.replace(
        b, signature=signer.sign(Domain.BALLOT, b.signed_bytes()))


def test_malfeasance_handler_parity_and_fallback():
    """The same proofs through (a) the sync fallback (farm=None) and
    (b) the farm path produce identical decisions; the fallback needs
    no event-loop machinery beyond the caller's."""
    prefix = b"vf-test"
    s = EdSigner(prefix=prefix)
    good = malfeasance.proof_from_ballots(_signed_ballot(s, 5, 1),
                                          _signed_ballot(s, 5, 2))
    bad = malfeasance.proof_from_ballots(_signed_ballot(s, 5, 1),
                                         _signed_ballot(s, 6, 2))
    forged = dataclasses.replace(good, sig2=bytes(64))

    def handler(farm):
        # fresh db per proof: condemning the identity once would make
        # every later proof short-circuit to "already known"
        return malfeasance.Handler(
            db=dbmod.open_state(), cache=AtxCache(),
            verifier=EdVerifier(prefix=prefix), pubsub=PubSub(),
            farm=farm)

    expected = [asyncio.run(handler(None).process_async(p))
                for p in (good, bad, forged)]
    assert expected == [True, False, False]

    async def main():
        farm = VerificationFarm(ed_verifier=EdVerifier(prefix=prefix))
        got = [await handler(farm).process_async(p)
               for p in (good, bad, forged)]
        await farm.aclose()
        return got

    assert asyncio.run(main()) == expected


# --- ed25519 batch verification (core/signing.py) -------------------------


def test_ed25519_rfc8032_vector():
    """RFC 8032 test vector 2 (msg = 0x72): pins the pure-Python
    fallback and the OpenSSL path to the same wire signatures, so nodes
    on containers with and without `cryptography` interoperate."""
    from spacemesh_tpu.core import signing

    seed = bytes.fromhex("4ccd089b28ff96da9db6c346ec114e0f"
                         "5b8a319f35aba624da8cf6ed4fb8a6fb")
    pk = bytes.fromhex("3d4017c3e843895a92b70aa74d1b7ebc"
                       "9c982ccf2ec4968cc0cd55f12af4660c")
    sig = bytes.fromhex(
        "92a009a9f0d4cab8720e820b5f642540"
        "a2b27b5416503f8fb3762223ebdb69da"
        "085ac1e43e15996e458f3613d0f11d8c"
        "387b2eaeb4302aeeb00d291612bb0c00")
    s = signing.EdSigner(seed=seed)  # prefix b"": raw RFC message
    assert s.public_key == pk
    # domain byte 0x72 + empty msg == the vector's one-byte message
    assert s.sign(0x72, b"") == sig
    v = signing.EdVerifier()
    assert v.verify(0x72, pk, b"", sig)
    assert not v.verify(0x72, pk, b"x", sig)


def test_ed25519_batch_verify_matches_serial():
    from spacemesh_tpu.core.signing import Domain, EdSigner, EdVerifier

    v = EdVerifier(prefix=b"bt")
    signers = [EdSigner(prefix=b"bt") for _ in range(3)]
    items = []
    for i in range(24):
        s = signers[i % 3]
        msg = b"bmsg" + i.to_bytes(2, "little")
        sig = s.sign(Domain.HARE, msg)
        if i % 5 == 0:
            sig = bytes(64) if i % 2 else sig[:40]  # invalid / malformed
        items.append((int(Domain.HARE), s.public_key, msg, sig))
    serial = [v.verify(d, p, m, g) for d, p, m, g in items]
    assert 0 < sum(serial) < len(serial)
    assert v.verify_many(items) == serial
    # all-valid fast path too (no fallback pass)
    valid = [it for it, ok in zip(items, serial) if ok]
    assert v.verify_many(valid) == [True] * len(valid)


def test_ed25519_torsion_defect_single_batch_parity():
    """An adversarial signature whose R carries a small-order torsion
    component: under the old cofactorless-single / RLC-batch split the
    batch accepted it with probability ~1/8 while single verify always
    rejected — nondeterministic farm-vs-inline divergence. Both paths
    are now cofactored (signing._ed_check) and must agree,
    deterministically, and accept it."""
    import hashlib

    from spacemesh_tpu.core import signing

    if signing._HAVE_CRYPTOGRAPHY:
        pytest.skip("OpenSSL backend (cofactorless) in use; this pins "
                    "the pure-Python cofactored path")

    # project an arbitrary curve point onto the torsion subgroup: Q*P
    # is P's small-order component (nonzero for ~7/8 of points)
    t8 = None
    i = 0
    while t8 is None:
        pt = signing._pt_decode(
            hashlib.sha256(b"torsion%d" % i).digest())
        i += 1
        if pt is None:
            continue
        cand = signing._pt_mul(signing._Q, pt)
        if not signing._pt_eq(cand, signing._ID):
            t8 = cand

    # forge: honest (r, s) but publish R' = R + T — the prime-order
    # part of the equation holds, the torsion part does not
    seed = bytes(31) + b"\x07"
    scalar, nonce_prefix = signing._expand_key(seed)
    pub = signing._pt_encode(signing._pt_mul_base(scalar))
    msg = b"torsion-msg"
    data = bytes([int(Domain.ATX)]) + msg
    r = int.from_bytes(
        hashlib.sha512(nonce_prefix + data).digest(),
        "little") % signing._Q
    r_enc = signing._pt_encode(
        signing._pt_add(signing._pt_mul_base(r), t8))
    k = int.from_bytes(
        hashlib.sha512(r_enc + pub + data).digest(),
        "little") % signing._Q
    s = (r + k * scalar) % signing._Q
    forged = r_enc + s.to_bytes(32, "little")

    v = EdVerifier()
    honest = EdSigner(seed=bytes(31) + b"\x09")
    items = [(int(Domain.ATX), pub, msg, forged)]
    for j in range(9):  # ≥8 candidates so the MSM batch path engages
        m = b"hm%d" % j
        items.append((int(Domain.ATX), honest.public_key, m,
                      honest.sign(Domain.ATX, m)))
    for _ in range(3):  # the old divergence was probabilistic
        signing.clear_verify_cache()
        batch = v.verify_many(items)
        signing.clear_verify_cache()
        serial = [v.verify(d, p, m, g) for d, p, m, g in items]
        assert batch == serial
        assert serial[0] is True  # pins the cofactored equation
    # a genuinely invalid signature still fails both paths
    bad = list(items[1])
    bad[3] = bytes(64)
    signing.clear_verify_cache()
    assert v.verify_many(items + [tuple(bad)])[-1] is False


# --- pubsub hardening (satellite) -----------------------------------------


def test_pubsub_raising_handler_does_not_block_others():
    from spacemesh_tpu.utils.metrics import pubsub_handler_drops

    ps = PubSub()
    seen = []

    async def bad(peer, data):
        raise RuntimeError("boom")

    async def good(peer, data):
        seen.append(data)
        return True

    ps.register("t1", bad)
    ps.register("t1", good)
    before = sum(pubsub_handler_drops._values.values())
    # a raising handler counts as a REJECT but must not stop delivery
    assert asyncio.run(ps.deliver("t1", b"p", b"payload")) is False
    assert seen == [b"payload"]
    assert sum(pubsub_handler_drops._values.values()) == before + 1
