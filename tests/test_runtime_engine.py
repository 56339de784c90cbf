"""Unit + equivalence coverage for the shared device-job runtime.

The engine (runtime/engine.py) replaced four hand-rolled copies of the
bounded dispatch->retire window; these tests pin its contracts — window
bound, FIFO retires, early exit, stop-discard, fallback — and then the
bit-identity of each migrated pipeline against its pre-runtime twin at
ragged totals (ISSUE 11 test satellite).  The lane-admission primitives
the farm now consumes (runtime/queue.py) get the review-fix semantics
asserted at unit level (the cancelled-waiter slot handoff).
"""

import asyncio
import enum
import hashlib

import numpy as np
import pytest

from spacemesh_tpu.runtime import engine
from spacemesh_tpu.runtime.queue import KindLanes, LaneGroup, QueueClosed
from spacemesh_tpu.utils import metrics


# --- Pipeline ----------------------------------------------------------


def test_pipeline_window_bound_and_fifo():
    depths = []
    retired = []
    pipe = engine.Pipeline(kind="t", inflight=3,
                           on_inflight=depths.append)
    res = pipe.run(range(10), dispatch=lambda i: i * 10,
                   retire=lambda t: retired.append(t))
    assert res is None
    assert retired == [i * 10 for i in range(10)]  # FIFO
    assert max(depths) == 3                        # bounded window
    assert pipe.stats.batches == 10
    assert not pipe.stats.early_exited and not pipe.stats.stopped


def test_pipeline_early_exit_abandons_inflight():
    dispatched = []
    retired = []

    def retire(t):
        retired.append(t)
        return "winner" if t == 2 else None

    pipe = engine.Pipeline(kind="t", inflight=3)
    res = pipe.run(iter(range(100)), dispatch=lambda i: dispatched.append(i)
                   or i, retire=retire)
    assert res == "winner"
    assert pipe.stats.early_exited
    # items 0,1,2 dispatched before the first retire could fire at
    # window depth 3; the early exit at ticket 2 stops the stream well
    # short of 100 and abandons the rest
    assert retired == [0, 1, 2]
    assert len(dispatched) < 10


def test_pipeline_stop_discards_pending():
    stop = [False]
    retired = []

    def dispatch(i):
        if i == 4:
            stop[0] = True
        return i

    pipe = engine.Pipeline(kind="t", inflight=8, stop=lambda: stop[0])
    res = pipe.run(range(10), dispatch, retired.append)
    assert res is None
    assert pipe.stats.stopped
    assert retired == []  # discarded, never retired


@pytest.mark.parametrize("case", ["full", "exit_in_stream",
                                  "exit_in_drain", "exit_on_idle",
                                  "stop_in_stream", "stop_in_drain"])
def test_pipeline_counts_the_tickets_it_abandons(case):
    """``stats.abandoned`` is every ticket dispatched and never retired,
    whichever of the five places dropped it (ISSUE 36)."""
    dispatched, retired = [], []
    stop = [False]
    n = 6
    items = list(range(n))
    if case == "exit_on_idle":
        items = [0, 1, engine.IDLE, 2]
    winner = {"exit_in_stream": 1, "exit_in_drain": n - 2,
              "exit_on_idle": 0}.get(case)

    def dispatch(i):
        dispatched.append(i)
        if (case == "stop_in_stream" and i == 3) or (
                case == "stop_in_drain" and i == n - 1):
            stop[0] = True
        return i

    def retire(t):
        retired.append(t)
        return "won" if t == winner else None

    pipe = engine.Pipeline(kind="t", inflight=3, stop=lambda: stop[0])
    pipe.run(iter(items), dispatch, retire)
    assert pipe.stats.abandoned == len(dispatched) - len(retired)
    assert (pipe.stats.abandoned > 0) == (case != "full"), retired


def test_pipeline_fallback_on_dispatch_failure():
    before = sum(metrics.runtime_fallbacks.sample().values())

    def dispatch(i):
        if i == 1:
            raise RuntimeError("device down")
        return ("dev", i)

    pipe = engine.Pipeline(kind="t", inflight=2,
                           fallback=lambda i, exc: ("host", i))
    out = []
    pipe.run(range(3), dispatch, out.append)
    assert out == [("dev", 0), ("host", 1), ("dev", 2)]
    assert pipe.stats.fallbacks == 1
    assert sum(metrics.runtime_fallbacks.sample().values()) == before + 1

    # without a fallback the exception propagates
    with pytest.raises(RuntimeError):
        engine.Pipeline(kind="t").run(range(3), dispatch, out.append)


def test_pipeline_sustained_failure_repays_device_without_breaker():
    """The pre-remediation regression, pinned: with no breaker there is
    no memory between batches — a permanently dead backend is re-paid
    the failing dispatch on EVERY batch."""
    attempts = [0]

    def dispatch(i):
        attempts[0] += 1
        raise RuntimeError("device permanently dead")

    pipe = engine.Pipeline(kind="t-nobreak", inflight=2,
                           fallback=lambda i, exc: ("host", i))
    pipe.run(range(50), dispatch, lambda t: None)
    assert attempts[0] == 50            # one failing attempt per batch
    assert pipe.stats.fallbacks == 50


def test_pipeline_breaker_stops_repaying_dead_device():
    """ISSUE 15 satellite: after the breaker trips, dispatch goes
    straight to fallback — exactly N device attempts for an M>>N-batch
    run, and runtime_fallbacks_total still counts every batch."""
    from spacemesh_tpu.obs import remediate

    clock = [0.0]  # frozen: the open breaker never reaches half-open
    br = remediate.CircuitBreaker("t-dev", failure_budget=3,
                                  window_s=60.0, cooldown_s=30.0,
                                  time_source=lambda: clock[0])
    attempts = [0]

    def dispatch(i):
        attempts[0] += 1
        raise RuntimeError("device permanently dead")

    before = sum(metrics.runtime_fallbacks.sample().values())
    out = []
    pipe = engine.Pipeline(kind="t-break", inflight=2, breaker=br,
                           fallback=lambda i, exc: ("host", i, exc))
    pipe.run(range(50), dispatch, out.append)
    assert attempts[0] == 3             # the budget, NOT one per batch
    assert len(out) == 50               # every batch still answered
    assert pipe.stats.fallbacks == 50
    assert sum(metrics.runtime_fallbacks.sample().values()) == before + 50
    assert br.state == remediate.OPEN
    # post-trip batches carry the typed BreakerOpen, not the stale
    # device error
    assert isinstance(out[-1][2], remediate.BreakerOpen)
    # device recovery: cooldown elapses, ONE probe re-closes, dispatch
    # resumes on the device path
    clock[0] = 100.0
    good = engine.Pipeline(kind="t-break", inflight=2, breaker=br,
                           fallback=lambda i, exc: ("host", i, exc))
    dev_out = []
    good.run(range(5), lambda i: ("dev", i), dev_out.append)
    assert good.stats.fallbacks == 0
    assert dev_out == [("dev", i) for i in range(5)]
    assert br.state == remediate.CLOSED


def test_pipeline_breaker_open_without_fallback_raises_typed():
    from spacemesh_tpu.obs import remediate

    br = remediate.CircuitBreaker("t-nofb", failure_budget=1,
                                  cooldown_s=30.0,
                                  time_source=lambda: 0.0)
    br.record_failure()
    with pytest.raises(remediate.BreakerOpen):
        engine.Pipeline(kind="t-nofb", breaker=br).run(
            range(3), lambda i: i, lambda t: None)


def test_pipeline_idle_sentinel_retires_without_dispatch():
    retired = []
    pipe = engine.Pipeline(kind="t", inflight=8)

    def items():
        yield 1
        yield 2
        assert pipe.pending_count == 2
        yield engine.IDLE      # retires 1
        yield engine.IDLE      # retires 2
        assert pipe.pending_count == 0
        yield engine.IDLE      # no-op on an empty window
        yield 3

    pipe.run(items(), dispatch=lambda i: i, retire=retired.append)
    assert retired == [1, 2, 3]


def test_pipeline_tenant_label_on_metrics():
    before = metrics.runtime_dispatched.sample().get(
        (("kind", "t-label"), ("tenant", "alice")), 0)
    pipe = engine.Pipeline(kind="t-label", tenant="alice", inflight=1)
    pipe.run(range(3), lambda i: i, lambda t: None)
    after = metrics.runtime_dispatched.sample()[
        (("kind", "t-label"), ("tenant", "alice"))]
    assert after == before + 3


# --- LaneGroup / KindLanes --------------------------------------------


class _L(enum.IntEnum):
    HI = 0
    LO = 1


class _Entry:
    def __init__(self, lane, deadline=0.0):
        self.lane = lane
        self.deadline = deadline


def test_lane_group_bounds_and_release():
    async def main():
        g = LaneGroup(_L, {_L.HI: 2, _L.LO: 1})
        g.bind(asyncio.get_running_loop())
        await g.acquire(_L.LO)   # room: returns immediately
        g.add(_L.LO)
        waiter = asyncio.ensure_future(g.acquire(_L.LO))
        await asyncio.sleep(0)
        assert not waiter.done()  # lane full: parked
        g.release(_L.LO)
        await asyncio.wait_for(waiter, 1)

    asyncio.run(main())


def test_lane_group_cancelled_waiter_hands_slot_on():
    """The PR-2 review-fix semantics, now asserted at the runtime
    layer: a waiter cancelled after release() resolved it must hand
    the freed slot to the next waiter."""

    async def main():
        g = LaneGroup(_L, {_L.HI: 1, _L.LO: 1})
        g.bind(asyncio.get_running_loop())
        g.add(_L.LO)  # full
        a = asyncio.ensure_future(g.acquire(_L.LO))
        b = asyncio.ensure_future(g.acquire(_L.LO))
        for _ in range(3):
            await asyncio.sleep(0)
        g.release(_L.LO)   # resolves a's waiter
        a.cancel()         # ...which a never consumes
        with pytest.raises(asyncio.CancelledError):
            await a
        await asyncio.wait_for(b, 1)  # hangs without the handoff

    asyncio.run(main())


def test_lane_group_close_fails_waiters():
    async def main():
        g = LaneGroup(_L, {_L.HI: 1, _L.LO: 1},
                      make_exc=lambda: QueueClosed("closed"))
        g.bind(asyncio.get_running_loop())
        g.add(_L.LO)
        w = asyncio.ensure_future(g.acquire(_L.LO))
        await asyncio.sleep(0)
        g.closed = True
        g.fail_waiters()
        with pytest.raises(QueueClosed):
            await w

    asyncio.run(main())


def test_kind_lanes_priority_and_promote():
    async def main():
        g = LaneGroup(_L, {_L.HI: 8, _L.LO: 8})
        g.bind(asyncio.get_running_loop())
        kl = KindLanes(g)
        lo1, lo2 = _Entry(_L.LO, 5.0), _Entry(_L.LO, 6.0)
        hi = _Entry(_L.HI, 9.0)
        for e in (lo1, lo2, hi):
            kl.append(e)
        assert kl.count() == 3 and g.total() == 3
        assert kl.earliest_deadline() == 5.0
        # promote lo2 to HI (the dedup-hit path): removed + re-added
        assert kl.remove(lo2)
        lo2.lane = _L.HI
        kl.append(lo2)
        batch = kl.take(10)
        assert batch == [hi, lo2, lo1]  # HI lane drains first
        assert not kl.remove(lo1)       # already taken

    asyncio.run(main())


# --- migrated-pipeline equivalence (pre-runtime twins) -----------------


def _host_vrf_nonce(label_bytes: bytes) -> int:
    halves = np.frombuffer(label_bytes, dtype="<u8").reshape(-1, 2)
    return int(np.lexsort((np.arange(halves.shape[0]),
                           halves[:, 0], halves[:, 1]))[0])


@pytest.mark.parametrize("total", [1, 7, 1000])
def test_initializer_on_engine_matches_reference(tmp_path, total):
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import LabelStore

    node = hashlib.sha256(b"rt-node").digest()
    commit = hashlib.sha256(b"rt-commit").digest()
    d = tmp_path / f"init-{total}"
    meta, res = initializer.initialize(
        d, node_id=node, commitment=commit, num_units=1,
        labels_per_unit=total, scrypt_n=2, max_file_size=1 << 20,
        batch_size=128)
    store = LabelStore(d, meta)
    got = store.read_labels(0, total)
    store.close()
    ref = scrypt.scrypt_labels(
        commit, np.arange(total, dtype=np.uint64), n=2).tobytes()
    assert got == ref
    assert meta.vrf_nonce == _host_vrf_nonce(ref)
    assert res.labels_written == total


def test_prover_on_engine_matches_serial_twin(tmp_path):
    from spacemesh_tpu.post import workload

    prover = workload.build(str(tmp_path / "st"), 1039, 256)
    pipelined = prover.prove(workload.CHALLENGE)
    serial = prover.prove_serial(workload.CHALLENGE)
    assert pipelined == serial
    assert workload.verify_proof(pipelined, 1039)


def test_prove_session_steps_match_inline(tmp_path):
    from spacemesh_tpu.post import workload

    prover = workload.build(str(tmp_path / "st"), 512, 256)
    session = prover.session(workload.CHALLENGE, tenant="alice")
    try:
        proof = None
        steps = 0
        while proof is None:
            proof = session.step()
            steps += 1
            assert steps < 100
        assert session.done
    finally:
        session.close()
    assert proof == prover.prove_serial(workload.CHALLENGE)
    # close is idempotent; a closed session refuses to step
    session.close()
    with pytest.raises(RuntimeError):
        session.step()


def test_k2pow_on_engine_matches_serial_twin():
    import jax.numpy as jnp

    from spacemesh_tpu.ops import pow as k2pow

    ch = hashlib.sha256(b"rt-pow-c").digest()
    nid = hashlib.sha256(b"rt-pow-n").digest()
    diff = bytes([0, 16]) + bytes([255]) * 30

    def serial(batch):
        st = jnp.asarray(k2pow.prefix_state(ch, nid))
        tgt = jnp.asarray(k2pow._words_be(diff))
        for i in range(1 << 16):
            nn = np.arange(i * batch, (i + 1) * batch, dtype=np.uint64)
            ok = np.asarray(k2pow.below_target_jit(
                k2pow.pow_hash_batch_jit(
                    st, jnp.asarray((nn & 0xFFFFFFFF).astype(np.uint32)),
                    jnp.asarray((nn >> 32).astype(np.uint32))), tgt))
            hits = np.nonzero(ok)[0]
            if hits.size:
                return int(nn[hits[0]])

    got = k2pow.search(ch, nid, diff, batch=2048)
    assert got == serial(2048)
    assert k2pow.verify(ch, nid, diff, got)
    # exhaustion is still None, not an exception
    assert k2pow.search(ch, nid, bytes(32), batch=64, max_batches=2) is None


def test_k2pow_device_failure_raises(monkeypatch):
    """No host re-hash unasked: a device failure in the k2pow search
    surfaces as the device's own error, and nothing counts a fallback."""
    from spacemesh_tpu.ops import pow as k2pow

    ch = hashlib.sha256(b"rt-pow-fb-c").digest()
    nid = hashlib.sha256(b"rt-pow-fb-n").digest()
    diff = bytes([0, 16]) + bytes([255]) * 30

    def boom(*a, **k):
        raise RuntimeError("device down")

    monkeypatch.setattr(k2pow, "pow_hash_batch_jit", boom)
    before = sum(metrics.runtime_fallbacks.sample().values())
    with pytest.raises(RuntimeError, match="device down"):
        k2pow.search(ch, nid, diff, batch=2048)
    assert sum(metrics.runtime_fallbacks.sample().values()) == before
